"""Serving benchmark of this repository: see ``bench/run.py``."""
