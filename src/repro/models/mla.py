"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

Prefill/train decompress the latent into per-head K/V (normal activation
cost); decode uses the ABSORBED form — W_UK folds into the query and W_UV
into the output so the per-step cost is O(S * kv_lora_rank) and the cache is
only (c_kv, k_rope): 2*(r + rope_dim) bytes/token/layer instead of
2*H*hd — the MLA memory saving the paper claims.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lora as lora_lib
from repro.models.layers import apply_rope, dense_init, norm_apply, norm_init, yarn_mscale

NEG_INF = -1e30


class MLACache(NamedTuple):
    c_kv: jnp.ndarray       # (B, S, r)      — compressed latent
    k_rope: jnp.ndarray     # (B, S, rope_d) — decoupled rope key (shared head)
    length: jnp.ndarray     # () int32


def mla_init(key, cfg: ModelConfig, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    d, H = cfg.d_model, cfg.num_heads
    p_dim = cfg.resolved_head_dim()          # qk nope dim
    v_dim = cfg.resolved_v_head_dim()
    r, rq, rd = cfg.kv_lora_rank, cfg.q_lora_rank, cfg.rope_head_dim
    keys = jax.random.split(key, 6)
    params = {
        "wkv_a": dense_init(keys[0], d, r + rd, dtype),
        "kv_norm": norm_init("rmsnorm", r, dtype),
        "wkv_b": dense_init(keys[1], r, H * (p_dim + v_dim), dtype),
        "wo": dense_init(keys[2], H * v_dim, d, dtype),
    }
    if rq:
        params["wq_a"] = dense_init(keys[3], d, rq, dtype)
        params["q_norm"] = norm_init("rmsnorm", rq, dtype)
        params["wq_b"] = dense_init(keys[4], rq, H * (p_dim + rd), dtype)
    else:
        params["wq"] = dense_init(keys[5], d, H * (p_dim + rd), dtype)
    return params


def _dense(params: dict, name: str, x):
    """``x @ params[name]``, plus its low-rank adapter where the block
    carries one (``models/lora.py``)."""
    y = x @ params[name]
    ad = params.get("lora", {}).get(name)
    if ad is not None:
        y = y + lora_lib.delta(ad, x)
    return y


def _weight(params: dict, name: str):
    """``params[name]`` with its adapter folded in (the absorbed decode
    reshapes the weight itself)."""
    w = params[name]
    ad = params.get("lora", {}).get(name)
    if ad is not None:
        w = w + (ad.a @ ad.b) * ad.scale
    return w


def softmax_scale(cfg: ModelConfig):
    """1/sqrt(qk head dim), times mscale(factor, mscale_all_dim)^2 under YaRN
    (DeepSeek-V2's ``softmax_scale``)."""
    scale = 1.0 / jnp.sqrt(jnp.float32(cfg.resolved_head_dim() + cfg.rope_head_dim))
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        m = yarn_mscale(ys.factor, ys.mscale_all_dim)
        scale = scale * (m * m)
    return scale


def _queries(cfg: ModelConfig, params: dict, x, positions):
    B, S, _ = x.shape
    H = cfg.num_heads
    p_dim, rd = cfg.resolved_head_dim(), cfg.rope_head_dim
    if cfg.q_lora_rank:
        cq = norm_apply("rmsnorm", params["q_norm"], x @ params["wq_a"], cfg.rms_norm_eps)
        q = cq @ params["wq_b"]
    else:
        q = _dense(params, "wq", x)
    q = q.reshape(B, S, H, p_dim + rd)
    q_nope, q_rope = q[..., :p_dim], q[..., p_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_rope


def _latent(cfg: ModelConfig, params: dict, x, positions):
    r, rd = cfg.kv_lora_rank, cfg.rope_head_dim
    kv_a = _dense(params, "wkv_a", x)
    c_kv = norm_apply("rmsnorm", params["kv_norm"], kv_a[..., :r], cfg.rms_norm_eps)
    k_rope = kv_a[..., r:][..., None, :]                  # single shared head
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta, cfg.rope_scaling)[..., 0, :]
    return c_kv, k_rope


def mla_forward(
    cfg: ModelConfig,
    params: dict,
    x: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    return_cache: bool = False,
    cache_len: int = 0,
) -> Tuple[jnp.ndarray, Optional[MLACache]]:
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    with jax.named_scope("lm/mla"):
        return _mla_forward(cfg, params, x, positions, return_cache, cache_len)


def _mla_forward(cfg, params, x, positions, return_cache, cache_len):
    B, S, _ = x.shape
    H = cfg.num_heads
    p_dim, v_dim, rd = cfg.resolved_head_dim(), cfg.resolved_v_head_dim(), cfg.rope_head_dim
    q_nope, q_rope = _queries(cfg, params, x, positions)
    c_kv, k_rope = _latent(cfg, params, x, positions)

    kv = _dense(params, "wkv_b", c_kv).reshape(B, S, H, p_dim + v_dim)
    k_nope, v = kv[..., :p_dim], kv[..., p_dim:]

    scale = softmax_scale(cfg)

    def block_attn(q_nope_b, q_rope_b, offset):
        """One query block vs the full keys: scores O(bq * S)."""
        bq = q_nope_b.shape[1]
        scores = (
            jnp.einsum("bqhp,bkhp->bhqk", q_nope_b, k_nope)
            + jnp.einsum("bqhp,bkp->bhqk", q_rope_b, k_rope)
        ).astype(jnp.float32) * scale
        qpos = offset + jnp.arange(bq)[:, None]
        kpos = jnp.arange(S)[None, :]
        scores = scores + jnp.where(kpos <= qpos, 0.0, NEG_INF)[None, None]
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        return jnp.einsum("bhqk,bkhv->bqhv", probs, v)

    BQ = 1024
    if S <= BQ:
        out = block_attn(q_nope, q_rope, 0)
    else:
        nb = -(-S // BQ)
        pad = nb * BQ - S
        qn = jnp.pad(q_nope, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q_nope
        qr = jnp.pad(q_rope, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q_rope
        qn = jnp.moveaxis(qn.reshape(B, nb, BQ, H, p_dim), 1, 0)
        qr = jnp.moveaxis(qr.reshape(B, nb, BQ, H, rd), 1, 0)

        def body(_, xs):
            i, qnb, qrb = xs
            return None, block_attn(qnb, qrb, i * BQ)

        _, outs = jax.lax.scan(body, None, (jnp.arange(nb), qn, qr))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, nb * BQ, H, v_dim)[:, :S]
    out = _dense(params, "wo", out.reshape(B, S, H * v_dim))

    cache = None
    if return_cache:
        slots = max(cache_len, S)
        ck, kr = c_kv, k_rope
        if slots > S:
            ck = jnp.pad(c_kv, ((0, 0), (0, slots - S), (0, 0)))
            kr = jnp.pad(k_rope, ((0, 0), (0, slots - S), (0, 0)))
        cache = MLACache(ck, kr, jnp.asarray(S, jnp.int32))
    return out, cache


def mla_empty_cache(cfg: ModelConfig, batch: int, max_len: int, dtype, length: int = 0) -> MLACache:
    c = jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype)
    kr = jnp.zeros((batch, max_len, cfg.rope_head_dim), dtype)
    return MLACache(c, kr, jnp.asarray(length, jnp.int32))


def mla_decode_step(
    cfg: ModelConfig,
    params: dict,
    x: jnp.ndarray,              # (B, 1, D)
    cache: MLACache,
) -> Tuple[jnp.ndarray, MLACache]:
    B = x.shape[0]
    H = cfg.num_heads
    p_dim, v_dim, rd = cfg.resolved_head_dim(), cfg.resolved_v_head_dim(), cfg.rope_head_dim
    r = cfg.kv_lora_rank
    pos = cache.length
    positions = jnp.broadcast_to(pos, (B, 1))

    q_nope, q_rope = _queries(cfg, params, x, positions)   # (B,1,H,*)
    c_new, kr_new = _latent(cfg, params, x, positions)     # (B,1,r), (B,1,rd)

    slots = cache.c_kv.shape[1]
    slot = jnp.minimum(pos, slots - 1)
    c_kv = jax.lax.dynamic_update_slice_in_dim(cache.c_kv, c_new, slot, axis=1)
    k_rope = jax.lax.dynamic_update_slice_in_dim(cache.k_rope, kr_new, slot, axis=1)

    w_b = _weight(params, "wkv_b").reshape(r, H, p_dim + v_dim)
    w_uk, w_uv = w_b[..., :p_dim], w_b[..., p_dim:]

    # absorbed: q_lat[b,h,r] = sum_p q_nope[b,h,p] * w_uk[r,h,p]
    q_lat = jnp.einsum("bqhp,rhp->bqhr", q_nope, w_uk)
    scale = softmax_scale(cfg)
    scores = (
        jnp.einsum("bqhr,bsr->bhqs", q_lat, c_kv)
        + jnp.einsum("bqhp,bsp->bhqs", q_rope, k_rope)
    ).astype(jnp.float32) * scale
    valid = jnp.arange(slots) <= pos
    scores = scores + jnp.where(valid, 0.0, NEG_INF)[None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)

    ctx_lat = jnp.einsum("bhqs,bsr->bqhr", probs, c_kv)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx_lat, w_uv)
    out = _dense(params, "wo", out.reshape(B, 1, H * v_dim))
    return out, MLACache(c_kv, k_rope, pos + 1)
