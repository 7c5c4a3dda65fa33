"""Blocked weighted bincount — the streaming-histogram scatter-add.

``repro.obs.hist`` folds every in-loop latency sample into fixed
log-spaced bins; the hot step is ``counts[idx[i]] += w[i]`` over a flat
batch of pre-binned indices. On TPU a data-dependent scatter serializes
badly, so the kernel walks the batch in ``(1, block_m)`` slabs over a
sequential grid and accumulates a one-hot-masked partial sum into a
single resident ``(1, num_bins)`` output block (the ``event_pop``
blocking pattern: every grid step maps to output block (0, 0), with a
``pl.when(b == 0)`` init).

Out-of-range indices are DROPPED (no lane of the one-hot compare
matches) — the caller bins with ``hist.bin_index`` which already clamps
into [0, bins], so a dropped index can only mean a caller bug, never a
silently-corrupted neighbouring bin.

The pure-lax oracle lives in ``kernels/ref.py`` (``hist_bincount_ref``)
and the dispatcher in ``kernels/ops.py`` (``hist_bincount``), following
the ``gossip_winner``/``delta_codec`` convention.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import interpret_mode

BLOCK_M = 512
_LANES = 128


def _bincount_kernel(idx_ref, w_ref, out_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    idx = idx_ref[...].astype(jnp.int32)        # (1, bm)
    w = w_ref[...].astype(jnp.int32)            # (1, bm)
    bm = idx.shape[1]
    nb = out_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, nb), 1)
    onehot = (idx.reshape(bm, 1) == cols).astype(jnp.int32)
    out_ref[...] += jnp.sum(
        onehot * w.reshape(bm, 1), axis=0, keepdims=True
    )


@functools.partial(
    jax.jit, static_argnames=("num_bins", "block_m", "interpret")
)
def hist_bincount_pallas(idx, weights, num_bins, block_m=BLOCK_M,
                         interpret=None):
    """(num_bins,) i32 weighted bincount of ``idx`` via the blocked kernel.

    ``idx`` i32 (m,) in [0, num_bins); ``weights`` i32 (m,). The batch is
    padded to a block multiple with an out-of-range index (dropped by the
    one-hot compare) and the bin axis to the 128-lane boundary.
    """
    (m,) = idx.shape
    bm = min(block_m, max(m, 1))
    m_pad = -(-max(m, 1) // bm) * bm
    nb_pad = -(-num_bins // _LANES) * _LANES
    idx = jnp.full((m_pad,), num_bins, jnp.int32).at[:m].set(
        idx.astype(jnp.int32)
    )
    w = jnp.zeros((m_pad,), jnp.int32).at[:m].set(
        weights.astype(jnp.int32)
    )
    nblocks = m_pad // bm
    out = pl.pallas_call(
        _bincount_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((1, bm), lambda b: (0, b)),
            pl.BlockSpec((1, bm), lambda b: (0, b)),
        ],
        out_specs=pl.BlockSpec((1, nb_pad), lambda b: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, nb_pad), jnp.int32),
        interpret=interpret_mode(interpret),
    )(idx.reshape(1, m_pad), w.reshape(1, m_pad))
    return out[0, :num_bins]
