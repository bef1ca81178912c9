"""Config registry: ``get_config("<arch-id>")`` -> ArchConfig."""

from __future__ import annotations

import importlib

from .base import SHAPES, ArchConfig, ShapeConfig  # noqa: F401

ARCH_IDS = [
    "recurrentgemma_9b",
    "llama3_2_1b",
    "qwen2_0_5b",
    "internlm2_1_8b",
    "qwen3_8b",
    "olmoe_1b_7b",
    "moonlight_16b_a3b",
    "mamba2_2_7b",
    "whisper_tiny",
    "paligemma_3b",
    "vusa_edge",  # the paper's own Edge-AI scale config
]


def _norm(arch: str) -> str:
    return arch.replace("-", "_").replace(".", "_")


def get_config(arch: str) -> ArchConfig:
    key = _norm(arch)
    if key not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    return importlib.import_module(f"repro.configs.{key}").CONFIG


def get_smoke_config(arch: str) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests."""
    key = _norm(arch)
    if key not in ARCH_IDS:
        raise KeyError(f"unknown arch {arch!r}; known: {ARCH_IDS}")
    return importlib.import_module(f"repro.configs.{key}").SMOKE
