#!/usr/bin/env python3
"""Readings behind a cell's correctness limit, many seeds in one process.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1,2,3

Runs the cell as ``bench/run.py`` does, once per seed, and prints one
JSON line per seed with the widest logit gap of the served tokens (the
program's reading) and of the control: the reference recomputed in the
precision one step below the cell's (``control`` in its traffic file),
read at the same positions of the same prompts and served tokens.  The
limit lies above the first over a dozen seeds and below the second.
The benchmark's own runs never compute the control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    run.use_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False, control=True,
                           t_process=time.perf_counter())
        c = res["check"]
        print(json.dumps({
            "seed": seed, "program": c["max_logit_gap"]["value"],
            "control": c["control_max_logit_gap"]["value"],
            "requests": c["requests_checked"],
            "tokens": c["tokens_checked"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
