"""Architecture config registry: ``--arch <id>`` resolution.

Each module defines ``full()`` (the exact published configuration) and
``smoke()`` (a reduced same-family config for CPU tests).
"""
from __future__ import annotations

import importlib
from typing import List

from repro.models.common import ModelConfig

ARCHS: List[str] = [
    "starcoder2_7b",
    "llama3_2_1b",
    "h2o_danube_3_4b",
    "qwen3_0_6b",
    "whisper_large_v3",
    "phi_3_vision_4_2b",
    "hymba_1_5b",
    "granite_moe_1b_a400m",
    "mixtral_8x7b",
    "xlstm_350m",
    "mellum2_12b_a2_5b",
    # the paper's own evaluation models
    "llama2_7b",
    "llama2_13b",
    "tinymistral_248m",
]

ASSIGNED: List[str] = ARCHS[:10]


def canon(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def get_config(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canon(name)}")
    return mod.full()


def get_smoke(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canon(name)}")
    return mod.smoke()
