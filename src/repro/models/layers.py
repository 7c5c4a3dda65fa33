"""Shared building blocks: norms, MLPs, RoPE, initializers.

Pure-functional: params are nested dicts of jnp arrays; every ``*_apply``
is vmappable over a leading params axis (needed by DAG-FL tip validation,
which evaluates a bank of candidate models with one vmap).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, fan_in: int, fan_out: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (fan_in, fan_out)) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.truncated_normal(key, -2.0, 2.0, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def norm_init(kind: str, d: int, dtype) -> dict:
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    if kind == "nonparam_layernorm":
        return {}
    raise ValueError(kind)


def norm_apply(kind: str, params: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    # nonparam_layernorm (OLMo): no affine params
    return y.astype(x.dtype)


def rms_head_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """QK-norm (Qwen3): RMS-normalise the last (head) dim."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (dense FFN)
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None, dtype=None) -> dict:
    d_ff = d_ff or cfg.d_ff
    dtype = dtype or jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    n_gate = 2 if cfg.act in ("swiglu", "geglu") else 1
    p = {"wo": dense_init(k2, d_ff, cfg.d_model, dtype)}
    p["wi"] = dense_init(k1, cfg.d_model, d_ff, dtype)
    if n_gate == 2:
        p["wg"] = dense_init(k3, cfg.d_model, d_ff, dtype)
    return p


def mlp_apply(cfg: ModelConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    h = x @ params["wi"]
    if cfg.act == "swiglu":
        h = jax.nn.silu(x @ params["wg"]) * h
    elif cfg.act == "geglu":
        h = jax.nn.gelu(x @ params["wg"], approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    return h @ params["wo"]


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature term, 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations: float, dim: int, theta: float, max_pos: int) -> float:
    return dim * math.log(max_pos / (rotations * 2 * math.pi)) / (2 * math.log(theta))


def yarn_freqs(head_dim: int, theta: float, scaling) -> jnp.ndarray:
    """YaRN inverse frequencies (arXiv:2309.00071), as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes them: dimensions that turn
    more than ``beta_fast`` times over the original context keep their
    frequency, those that turn fewer than ``beta_slow`` times are divided by
    ``factor``, and a linear ramp blends the ones between."""
    orig = scaling.original_max_position_embeddings
    low = max(math.floor(_yarn_correction_dim(scaling.beta_fast, head_dim, theta, orig)), 0)
    high = min(math.ceil(_yarn_correction_dim(scaling.beta_slow, head_dim, theta, orig)),
               head_dim - 1)
    if low == high:
        high += 0.001
    pos = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    extra = 1.0 / (theta ** pos)
    inter = 1.0 / (scaling.factor * theta ** pos)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return inter * ramp + extra * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling=None) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).

    ``scaling`` (a ``YarnScaling``) switches to YaRN frequencies, with cos
    and sin scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    hd = x.shape[-1]
    if scaling is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    else:
        freqs = yarn_freqs(hd, theta, scaling)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin = jnp.sin(ang)[..., :, None, :]                 # (..., S, 1, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            sin, cos = sin * m, cos * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------


def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray, mask=None) -> jnp.ndarray:
    """Mean next-token cross entropy. logits (..., V), labels int (...)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
