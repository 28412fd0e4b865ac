"""Architecture registry: 11 archs x 4 input shapes.

``get_config(name)`` / ``get_smoke(name)`` return the exact published config
(or its reduced smoke twin).  ``config_for_shape`` applies per-cell variants
(e.g. gemma3 + long_500k enables the paper's landmark decode on the global
layers).  ``cells()`` enumerates every (arch, shape) dry-run cell, honouring
the long_500k skip rule for pure full-attention archs.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Iterator, List, Tuple

from repro.configs.base import (  # noqa: F401
    LONG_CONTEXT_OK,
    SHAPES,
    ModelConfig,
    ShapeConfig,
    input_specs,
)

_MODULES = {
    "xlstm-125m": "repro.configs.xlstm_125m",
    "gemma3-12b": "repro.configs.gemma3_12b",
    "minitron-4b": "repro.configs.minitron_4b",
    "yi-9b": "repro.configs.yi_9b",
    "yi-6b": "repro.configs.yi_6b",
    "deepseek-v3-671b": "repro.configs.deepseek_v3_671b",
    "qwen2-moe-a2.7b": "repro.configs.qwen2_moe_a2_7b",
    "chameleon-34b": "repro.configs.chameleon_34b",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "mellum2-12b-a2.5b": "repro.configs.mellum2_12b_a2_5b",
}

ARCHS: List[str] = list(_MODULES)


def _module(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; one of {ARCHS}")
    return importlib.import_module(_MODULES[name])


def get_config(name: str) -> ModelConfig:
    return _module(name).FULL


def get_smoke(name: str) -> ModelConfig:
    return _module(name).SMOKE


def config_for_shape(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    """Per-cell config variants.

    - long_500k on gemma3: global layers decode through the paper's landmark
      (fast-SPSD) attention — the full KV cache for 500k tokens would be
      quadratic-time to attend and the landmark state is O(c) instead.
    - decode cells on MoE archs keep the gather dispatch (token batch of 1
      per step does not amortize an all_to_all).
    """
    if shape.name == "long_500k" and cfg.name.startswith("gemma3"):
        return dataclasses.replace(cfg, use_landmark_decode=True)
    return cfg


def shapes_for(name: str) -> List[ShapeConfig]:
    out = []
    for s in SHAPES.values():
        if s.name == "long_500k" and name not in LONG_CONTEXT_OK:
            continue
        out.append(s)
    return out


def cells() -> Iterator[Tuple[str, ShapeConfig]]:
    for a in ARCHS:
        for s in shapes_for(a):
            yield a, s
