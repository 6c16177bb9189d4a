"""Assigned-architecture registry: ``--arch <id>`` resolves here.

Each module exposes ``config()`` (the exact published configuration) and
``smoke_config()`` (a reduced same-family config for CPU smoke tests).
"""
from __future__ import annotations

import importlib
from typing import List

from ..models.config import ModelConfig

ARCH_IDS: List[str] = [
    "rwkv6-3b",
    "minicpm3-4b",
    "qwen2-0.5b",
    "qwen3-32b",
    "llama3-405b",
    "olmoe-1b-7b",
    "grok-1-314b",
    "recurrentgemma-9b",
    "qwen2-vl-2b",
    "seamless-m4t-medium",
    "deepseek-v2-lite",
]


def _module(arch_id: str):
    mod_name = arch_id.replace("-", "_").replace(".", "_")
    return importlib.import_module(f".{mod_name}", __package__)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    cfg = _module(arch_id).config()
    cfg.validate()
    return cfg


def get_smoke_config(arch_id: str) -> ModelConfig:
    if arch_id not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}")
    cfg = _module(arch_id).smoke_config()
    cfg.validate()
    return cfg
