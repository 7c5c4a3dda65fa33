"""Pallas kernel: chunk-parallel RWKV6 WKV with data-dependent decay.

The rwkv6 hot spot (DESIGN.md §3): the recurrence

    y_t = r_t @ (S_{t-1} + diag(u) k_t^T v_t),  S_t = diag(w_t) S_{t-1} + k_t^T v_t

processed one (B, H) tile per grid step with the (hd, hd) state resident in
VMEM across the whole time loop — the TPU analogue of the CUDA kernel's
register-resident state. Within each CHUNK timesteps the pairwise decay
tensor (C, C, hd) is formed in VMEM and contracted on the MXU (all its
exponents are <= 0, so no rescaling pass is needed — see models/rwkv.py).

Grid: (B, H, T/CHUNK); chunk axis innermost so the state scratch persists.
Oracle: repro.models.rwkv.wkv_scan (sequential), cross-checked against
wkv_chunked in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import interpret_mode

CHUNK = 32


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *, chunk, nc):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    rb = r_ref[0, 0].astype(jnp.float32)          # (C, hd)
    kb = k_ref[0, 0].astype(jnp.float32)
    vb = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)         # log decay <= 0
    u = u_ref[0].astype(jnp.float32)              # (hd,)
    C = chunk

    cum = jnp.cumsum(lw, axis=0)                  # inclusive (C, hd)
    cum_prev = cum - lw                           # exclusive
    # intra-chunk pairwise decay W[t, s, :] = exp(cum_prev[t] - cum[s]), s < t
    expo = cum_prev[:, None, :] - cum[None, :, :]              # (C, C, hd)
    mask = (
        jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        > jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    )[..., None]
    W = jnp.where(mask, jnp.exp(expo), 0.0)

    scores = jnp.einsum("td,sd,tsd->ts", rb, kb, W)            # (C, C)
    bonus = jnp.sum(rb * kb * u[None, :], axis=1)              # (C,)
    y = jax.lax.dot_general(
        scores, vb, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = y + bonus[:, None] * vb

    # inter-chunk: read the carried state
    S = s_ref[...]                                             # (hd, hd)
    rdec = rb * jnp.exp(cum_prev)
    y = y + jax.lax.dot_general(
        rdec, S, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0, 0] = y.astype(o_ref.dtype)

    # state update: S' = exp(cum_C) * S + sum_s (k_s * exp(cum_C - cum_s)) v_s^T
    total = cum[-1]                                            # (hd,)
    kdec = kb * jnp.exp(total[None, :] - cum)
    s_ref[...] = jnp.exp(total)[:, None] * S + jax.lax.dot_general(
        kdec, vb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv_pallas(
    r: jnp.ndarray,      # (B, T, H, hd)
    k: jnp.ndarray,
    v: jnp.ndarray,
    logw: jnp.ndarray,   # (B, T, H, hd), log decay <= 0
    u: jnp.ndarray,      # (H, hd)
    chunk: int = CHUNK,
    interpret: bool = None,
) -> jnp.ndarray:
    B, T, H, hd = r.shape
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    def arrange(x):
        # (B, T, H, hd) -> (B, H, T, hd) so the chunk dim tiles cleanly
        return jnp.moveaxis(x, 2, 1)

    rr, kk, vv, ww = map(arrange, (r, k, v, logw))
    kernel = functools.partial(_wkv_kernel, chunk=chunk, nc=nc)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hd), lambda b, h, c: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(rr, kk, vv, ww, u)
    return jnp.moveaxis(out, 1, 2)                 # back to (B, T, H, hd)
