"""Config registry: ``get_config(arch_id)`` / ``get_reduced(arch_id)``.

Arch ids match the assignment table; ``lumos5g-lstm`` is the paper's own PoC.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import LSTMConfig, ModelConfig, ShapeConfig, SplitConfig, TrainConfig
from repro.configs.shapes import SHAPES, get_shape

_MODULES: Dict[str, str] = {
    "musicgen-large": "repro.configs.musicgen_large",
    "stablelm-3b": "repro.configs.stablelm_3b",
    "llava-next-34b": "repro.configs.llava_next_34b",
    "qwen2.5-3b": "repro.configs.qwen2_5_3b",
    "phi3.5-moe-42b-a6.6b": "repro.configs.phi3_5_moe",
    "mixtral-8x7b": "repro.configs.mixtral_8x7b",
    "internlm2-20b": "repro.configs.internlm2_20b",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "granite-8b": "repro.configs.granite_8b",
    "xlstm-125m": "repro.configs.xlstm_125m",
    "mellum2-12b-a2.5b": "repro.configs.mellum2_12b",
    "lumos5g-lstm": "repro.configs.lumos5g_lstm",
}

ARCH_IDS: List[str] = [k for k in _MODULES if k != "lumos5g-lstm"]


def get_config(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch]).CONFIG


def get_reduced(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(_MODULES[arch]).reduced()


__all__ = [
    "ARCH_IDS", "SHAPES", "LSTMConfig", "ModelConfig", "ShapeConfig",
    "SplitConfig", "TrainConfig", "get_config", "get_reduced", "get_shape",
]
