"""Pallas TPU flash attention: causal/sliding-window prefill + GQA decode.

Prefill kernel: grid (B, H, num_q_blocks, num_k_blocks); online-softmax
accumulators (m, l, acc) live in VMEM scratch and persist across the
innermost k-block dimension; fully-masked k-blocks (beyond causal frontier
or outside the sliding window) skip their compute. Block shapes are
(8,128)-aligned; the MXU sees (bq, hd) x (hd, bk) matmuls.

Decode kernel: one query per (batch, kv-head) group against an S-slot cache,
grid (B, KV, num_s_blocks), same online softmax; GQA groups share the kv
block so each cache byte is read once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import interpret_mode

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, bq, bk, nk, window, scale):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q_lo = iq * bq
    k_lo = ik * bk
    # causal frontier: any k in block usable by any q in block?
    needed = k_lo <= q_lo + bq - 1
    if window:
        needed = jnp.logical_and(needed, k_lo + bk - 1 > q_lo - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                        # (bq, bk)
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos <= q_pos
        if window:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_s[...]                                # (bq, 128) replicated
        l_prev = l_s[...]
        m_cur = jnp.max(s, axis=1)[:, None]              # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)                  # (bq, 128)
        p = jnp.exp(s - m_new[:, :1])                    # (bq, bk)
        l_new = alpha * l_prev + jnp.broadcast_to(
            jnp.sum(p, axis=1)[:, None], l_prev.shape
        )
        acc_s[...] = acc_s[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_s[...] = m_new
        l_s[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = l_s[...][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0] = (acc_s[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_q", "block_k", "interpret")
)
def flash_attention_pallas(
    q: jnp.ndarray,   # (B, H, S, hd)
    k: jnp.ndarray,   # (B, KV, S, hd)
    v: jnp.ndarray,   # (B, KV, S, hd)
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = None,
) -> jnp.ndarray:
    B, H, S, hd = q.shape
    KV = k.shape[1]
    bq, bk = min(block_q, S), min(block_k, S)
    pad_q = (-S) % bq
    pad_k = (-S) % bk
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sq, Sk = S + pad_q, S + pad_k
    nq, nk = Sq // bq, Sk // bk
    group = H // KV
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, window=window, scale=scale
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
    return out[:, :, :S, :]


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, bs, ns, scale):
    isb = pl.program_id(2)

    @pl.when(isb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    length = len_ref[0, 0]
    s_lo = isb * bs

    @pl.when(s_lo < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                        # (G, bs)
        pos = s_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev, l_prev = m_s[...], l_s[...]
        m_cur = jnp.max(s, axis=1)[:, None]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_s[...] = alpha * l_prev + jnp.broadcast_to(jnp.sum(p, axis=1)[:, None], l_prev.shape)
        acc_s[...] = acc_s[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_s[...] = m_new

    @pl.when(isb == ns - 1)
    def _finalize():
        denom = l_s[...][:, :1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0, 0] = (acc_s[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_pallas(
    q: jnp.ndarray,        # (B, H, hd)
    k: jnp.ndarray,        # (B, S, KV, hd)
    v: jnp.ndarray,        # (B, S, KV, hd)
    lengths: jnp.ndarray,  # (B,) int32
    block_s: int = 512,
    interpret: bool = None,
) -> jnp.ndarray:
    B, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    bs = min(block_s, S)
    pad = (-S) % bs
    kk = jnp.moveaxis(k, 2, 1)                           # (B, KV, S, hd)
    vv = jnp.moveaxis(v, 2, 1)
    if pad:
        kk = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vv = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0)))
    ns = (S + pad) // bs
    qg = q.reshape(B, KV, G, hd)
    lens = lengths.reshape(B, 1).astype(jnp.int32)
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_decode_kernel, bs=bs, ns=ns, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(B, KV, ns),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, g, s: (b, 0)),
            pl.BlockSpec((1, 1, G, hd), lambda b, g, s: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, s: (b, g, s, 0)),
            pl.BlockSpec((1, 1, bs, hd), lambda b, g, s: (b, g, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, g, s: (b, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(lens, qg, kk, vv)
    return out.reshape(B, H, hd)
