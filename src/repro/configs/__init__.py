"""Architecture registry: one module per assigned arch (+ the paper's own
DistilBERT-MLM).  ``get_config("qwen2-7b")`` / ``--arch qwen2-7b``."""

from __future__ import annotations

import importlib
from typing import Dict, List

from repro.models.config import ModelConfig

_MODULES = {
    "qwen2-7b": "qwen2_7b",
    "rwkv6-1.6b": "rwkv6_1_6b",
    "qwen3-14b": "qwen3_14b",
    "nemotron-4-340b": "nemotron_4_340b",
    "whisper-tiny": "whisper_tiny",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "llama-3.2-vision-90b": "llama_3_2_vision_90b",
    "zamba2-1.2b": "zamba2_1_2b",
    "granite-4.0-h-micro": "granite_4_0_h_micro",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "distilbert-mlm": "distilbert_mlm",
}

ASSIGNED: List[str] = [k for k in _MODULES if k != "distilbert-mlm"]


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {k: get_config(k) for k in _MODULES}
