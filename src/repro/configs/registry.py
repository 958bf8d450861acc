"""Architecture registry: resolves ``--arch <id>`` to (ARCH, SMOKE) configs."""

from __future__ import annotations

import importlib
from typing import Dict, Tuple

from repro.configs.base import ArchConfig, SHAPES, ShapeConfig

_MODULES: Dict[str, str] = {
    "phi3.5-moe-42b-a6.6b": "repro.configs.phi3_5_moe_42b",
    "phi3-medium-14b": "repro.configs.phi3_medium_14b",
    "rwkv6-3b": "repro.configs.rwkv6_3b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t",
    "internvl2-26b": "repro.configs.internvl2_26b",
    "starcoder2-3b": "repro.configs.starcoder2_3b",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "qwen2.5-14b": "repro.configs.qwen2_5_14b",
    "command-r-plus-104b": "repro.configs.command_r_plus_104b",
    "whisper-medium": "repro.configs.whisper_medium",
    "moonlight-16b-a3b": "repro.configs.moonlight_16b_a3b",
    "paper-transformer-base": "repro.configs.paper_transformer",
}

ASSIGNED_ARCHS = tuple(k for k in _MODULES if k != "paper-transformer-base")


def arch(name: str) -> ArchConfig:
    return importlib.import_module(_MODULES[name]).ARCH


def smoke(name: str) -> ArchConfig:
    return importlib.import_module(_MODULES[name]).SMOKE


def shape(name: str) -> ShapeConfig:
    return SHAPES[name]


def all_archs() -> Dict[str, ArchConfig]:
    return {k: arch(k) for k in _MODULES}
