"""Architecture config registry.

``get_config(arch_id)`` returns the exact assigned configuration;
``get_reduced(arch_id)`` returns the CPU smoke-test variant of the same
family. ``ARCHS`` lists the 10 assigned architectures (clip-b32 — the
paper's own backbone — and jamba2-mini, the hybrid SSM/MoE client on the
chip, are additionally registered).
"""
from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig, InputShape, INPUT_SHAPES  # noqa: F401

_MODULES = {
    "yi-9b": "yi_9b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b_a22b",
    "h2o-danube-3-4b": "h2o_danube_3_4b",
    "whisper-medium": "whisper_medium",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "llava-next-34b": "llava_next_34b",
    "codeqwen1.5-7b": "codeqwen1_5_7b",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "starcoder2-15b": "starcoder2_15b",
    "clip-b32": "clip_b32",
    "jamba2-mini": "jamba2_mini",
}

# the assigned ten; clip-b32 and jamba2-mini are registered besides
ARCHS = tuple(k for k in _MODULES if k not in ("clip-b32", "jamba2-mini"))


def _module(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}")


def get_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_reduced(arch: str) -> ModelConfig:
    return _module(arch).reduced()
