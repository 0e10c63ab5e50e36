"""Architecture registry: ``--arch <id>`` resolves here."""
from __future__ import annotations

from repro.configs.base import (
    ModelConfig,
    ServeConfig,
    ShapeConfig,
    TrainConfig,
    SHAPES,
    SHAPES_BY_NAME,
    reduced,
)

from repro.configs.gemma_2b import CONFIG as _gemma_2b
from repro.configs.gemma2_27b import CONFIG as _gemma2_27b
from repro.configs.qwen25_14b import CONFIG as _qwen25_14b
from repro.configs.qwen2_72b import CONFIG as _qwen2_72b
from repro.configs.mamba2_130m import CONFIG as _mamba2_130m
from repro.configs.musicgen_medium import CONFIG as _musicgen_medium
from repro.configs.qwen3_moe_235b import CONFIG as _qwen3_moe
from repro.configs.phi35_moe import CONFIG as _phi35_moe
from repro.configs.zamba2_7b import CONFIG as _zamba2_7b
from repro.configs.internvl2_76b import CONFIG as _internvl2_76b
from repro.configs.llada_8b import CONFIG as _llada_8b
from repro.configs.llada_8b_1chip import CONFIG as _llada_8b_1chip

ARCHS = {
    "gemma-2b": _gemma_2b,
    "gemma2-27b": _gemma2_27b,
    "qwen2.5-14b": _qwen25_14b,
    "qwen2-72b": _qwen2_72b,
    "mamba2-130m": _mamba2_130m,
    "musicgen-medium": _musicgen_medium,
    "qwen3-moe-235b-a22b": _qwen3_moe,
    "phi3.5-moe-42b-a6.6b": _phi35_moe,
    "zamba2-7b": _zamba2_7b,
    "internvl2-76b": _internvl2_76b,
    # the paper's own model (not part of the assigned 10, used by examples)
    "llada-8b": _llada_8b,
    # its published widths at half depth, sized for one 16 GB TPU v5e chip
    "llada-8b-1chip": _llada_8b_1chip,
}

ASSIGNED = tuple(k for k in ARCHS if not k.startswith("llada-8b"))


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def list_archs():
    return sorted(ARCHS)


__all__ = [
    "ModelConfig", "ServeConfig", "ShapeConfig", "TrainConfig",
    "SHAPES", "SHAPES_BY_NAME", "ARCHS", "ASSIGNED",
    "get_config", "list_archs", "reduced",
]
