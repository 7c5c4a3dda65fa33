"""Pallas kernel: pairwise squared-L2 distances between k flattened models.

Feeds DAG-FL anomaly detection (parameter-space outlier scoring of tips —
poisoned models sit far from the normal cluster). Streaming MXU pattern:
grid over N blocks, (k, k) output block revisited and accumulated each step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import interpret_mode

BLOCK_N = 16 * 1024


def _dist_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)                  # (k, bn)
    sq = jnp.sum(x * x, axis=1)                         # (k,)
    cross = jax.lax.dot_general(                        # (k, k) on the MXU
        x, x, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[...] += sq[:, None] + sq[None, :] - 2.0 * cross


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def model_distance_pallas(
    models: jnp.ndarray,         # (k, N)
    block_n: int = BLOCK_N,
    interpret: bool = None,
) -> jnp.ndarray:
    k, n = models.shape
    pad = (-n) % block_n
    x = jnp.pad(models, ((0, 0), (0, pad)))             # zero pad: dist-safe
    n_pad = n + pad

    return pl.pallas_call(
        _dist_kernel,
        grid=(n_pad // block_n,),
        in_specs=[pl.BlockSpec((k, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((k, k), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, k), jnp.float32),
        interpret=interpret_mode(interpret),
    )(x)
