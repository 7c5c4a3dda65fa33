"""Pallas kernel: Eq.-(1) FederatedAveraging over k candidate models.

The DAG-FL per-iteration hot spot: a memory-bound streaming reduction
``out[n] = sum_k w[k] * models[k, n]`` over the flattened parameter vector.
Tiled so each grid step holds a (k, BLOCK_N) slab in VMEM; k is tiny (2..8)
so the slab is written (8, 128)-aligned in N.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import interpret_mode

BLOCK_N = 16 * 1024  # 16k f32 lanes x k rows ~= 512 KiB @ k=8 — fits VMEM


def _fedavg_kernel(w_ref, x_ref, o_ref):
    # w_ref: (k, 1) f32; x_ref: (k, BLOCK_N); o_ref: (1, BLOCK_N)
    w = w_ref[...].astype(jnp.float32)                  # (k, 1)
    x = x_ref[...].astype(jnp.float32)                  # (k, bn)
    o_ref[...] = jnp.sum(w * x, axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def fedavg_pallas(
    weights: jnp.ndarray,        # (k,) f32
    models: jnp.ndarray,         # (k, N)
    block_n: int = BLOCK_N,
    interpret: bool = None,
) -> jnp.ndarray:
    k, n = models.shape
    pad = (-n) % block_n
    x = jnp.pad(models, ((0, 0), (0, pad)))
    n_pad = n + pad
    w = weights.reshape(k, 1).astype(jnp.float32)

    out = pl.pallas_call(
        _fedavg_kernel,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, block_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), models.dtype),
        interpret=interpret_mode(interpret),
    )(w, x)
    return out[0, :n]
