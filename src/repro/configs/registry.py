"""Architecture registry: ``--arch <id>`` resolution for every launcher."""
from __future__ import annotations

from typing import Dict, List

from repro.configs.base import ModelConfig, SHAPES, ShapeSpec

from repro.configs.olmo_1b import CONFIG as _olmo_1b
from repro.configs.deepseek_v2_236b import CONFIG as _deepseek_v2
from repro.configs.deepseek_v2_lite import CONFIG as _deepseek_v2_lite
from repro.configs.gemma_2b import CONFIG as _gemma_2b
from repro.configs.qwen3_0_6b import CONFIG as _qwen3
from repro.configs.kimi_k2_1t_a32b import CONFIG as _kimi_k2
from repro.configs.musicgen_large import CONFIG as _musicgen
from repro.configs.paligemma_3b import CONFIG as _paligemma
from repro.configs.rwkv6_7b import CONFIG as _rwkv6
from repro.configs.zamba2_2_7b import CONFIG as _zamba2
from repro.configs.qwen2_5_14b import CONFIG as _qwen2_5

ARCHS: Dict[str, ModelConfig] = {
    cfg.name: cfg
    for cfg in (
        _olmo_1b,
        _deepseek_v2,
        _deepseek_v2_lite,
        _gemma_2b,
        _qwen3,
        _kimi_k2,
        _musicgen,
        _paligemma,
        _rwkv6,
        _zamba2,
        _qwen2_5,
    )
}

# Architectures whose full replica cannot live on one 16-device model group of
# v5e (16 GB HBM) -> DAG-FL node granularity is a whole pod (DESIGN.md §5).
POD_GRANULARITY = frozenset({"deepseek-v2-236b", "kimi-k2-1t-a32b"})


def get_arch(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


def get_shape(name: str) -> ShapeSpec:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}")
    return SHAPES[name]


def list_archs() -> List[str]:
    return sorted(ARCHS)


def long_context_variant(cfg: ModelConfig) -> ModelConfig:
    """Return the sub-quadratic variant used for ``long_500k`` (DESIGN.md §6).

    SSM/hybrid archs are already sub-quadratic; full-attention archs switch to
    the sliding-window attention variant (bounded KV cache). MLA keeps its
    latent cache but also windows at 500k.
    """
    from dataclasses import replace

    if cfg.sub_quadratic():
        return cfg
    return replace(cfg, attention="sliding_window", window_size=8192)


def pairs_for_dryrun():
    """All (arch, shape) combinations with the long_500k policy applied."""
    out = []
    for arch in list_archs():
        cfg = get_arch(arch)
        for shape_name, shape in SHAPES.items():
            mcfg = cfg
            if shape_name == "long_500k":
                mcfg = long_context_variant(cfg)
            out.append((arch, shape_name, mcfg, shape))
    return out
