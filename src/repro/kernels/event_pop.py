"""Pallas kernel: masked lexicographic argmin over the event queue.

The continuous-time hot spot (``repro.net.events``): every iteration of the
device-resident event loop must find the next event to fire — the valid
queue slot with the smallest ``(time, kind, seq)`` key. That is the
``gossip_merge`` reduction with min in place of max: a masked lexicographic
reduction over one axis, no data-dependent shapes, so the whole horizon
stays inside one jitted ``lax.while_loop``.

The kernel lays the queue out as (rows, 128) lanes and reduces it in
``(block_rows, 128)`` slabs, ``block_rows`` a multiple of the 8-row
sublane tile: grid step ``b`` reduces its slab to a local
``(time, kind, seq, idx)`` best, and the wrapper folds the per-slab bests
in lax — a handful of values, so the grid carries no state across steps.
Padding slots are invalid and can never win.
``repro.kernels.ref.event_pop_ref`` is the pure-lax oracle/CPU fast path;
equivalence is property-tested in ``tests/test_net_events.py``. The kernel
runs compiled on TPU and in the Pallas interpreter elsewhere
(``repro.kernels.dispatch``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.dispatch import interpret_mode, pick_impl

LANES = 128
BLOCK_Q = 16384   # (128, 128) slab x 4 inputs: 256 KiB VMEM


def _min_all(x):
    """(rows, lanes) -> (1, 1) min, kept 2-D for the TPU vector unit."""
    return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _pop_kernel(t_ref, k_ref, s_ref, v_ref, bt_ref, bk_ref, bs_ref, bi_ref):
    # t/k/s/v_ref: (br, 128) — this step's queue slab (time, kind, seq, valid)
    # b*_ref: (1, 1, 128) — the slab's best (time, kind, seq, global idx),
    # broadcast over the lanes; idx = int32 max marks an all-invalid slab
    b = pl.program_id(0)
    br, lanes = t_ref.shape
    imax = jnp.iinfo(jnp.int32).max
    v = v_ref[...] != 0
    t = jnp.where(v, t_ref[...], jnp.inf)
    bt = _min_all(t)
    tie = v & (t == bt)
    kk = jnp.where(tie, k_ref[...], imax)
    bk = _min_all(kk)
    tie = tie & (kk == bk)
    ss = jnp.where(tie, s_ref[...], imax)
    bs = _min_all(ss)
    tie = tie & (ss == bs)
    row = jax.lax.broadcasted_iota(jnp.int32, tie.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, tie.shape, 1)
    idx = (b * br + row) * lanes + lane
    bi = _min_all(jnp.where(tie, idx, imax))
    for ref, val in ((bt_ref, bt), (bk_ref, bk), (bs_ref, bs), (bi_ref, bi)):
        ref[0] = jnp.broadcast_to(val, (1, lanes))


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def event_pop_pallas(
    time: jnp.ndarray,      # (Q,) f32
    kind: jnp.ndarray,      # (Q,) i32
    seq: jnp.ndarray,       # (Q,) i32
    valid: jnp.ndarray,     # (Q,) bool
    block_q: int = BLOCK_Q,
    interpret: bool = None,
):
    """(idx () i32, found () bool) — the queue-head reduction as a kernel.

    ``block_q`` is the slab size in slots, a multiple of 8 x 128; a queue
    that fits one slab is reduced in a single step. Padding slots arrive
    invalid (they can never win); an all-invalid queue leaves the idx
    sentinel untouched, which the wrapper folds into ``found`` so the
    outputs are bitwise ``ref.event_pop_ref``.
    """
    if block_q % (8 * LANES):
        raise ValueError(f"block_q={block_q} must be a multiple of {8 * LANES}")
    q = time.shape[0]
    rows = -(-max(q, 1) // LANES)
    br = min(block_q // LANES, -(-rows // 8) * 8)
    nb = -(-rows // br)
    pad = nb * br * LANES - q

    def slab(x, dtype, fill=0):
        x = jnp.pad(jnp.asarray(x, dtype), (0, pad), constant_values=fill)
        return x.reshape(nb * br, LANES)

    outs = pl.pallas_call(
        _pop_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((br, LANES), lambda b: (b, 0))
                  for _ in range(4)],
        out_specs=[pl.BlockSpec((1, 1, LANES), lambda b: (b, 0, 0))
                   for _ in range(4)],
        out_shape=[jax.ShapeDtypeStruct((nb, 1, LANES), dt) for dt in
                   (jnp.float32, jnp.int32, jnp.int32, jnp.int32)],
        interpret=interpret_mode(interpret),
    )(slab(time, jnp.float32, jnp.inf), slab(kind, jnp.int32),
      slab(seq, jnp.int32), slab(valid, jnp.int32))
    bt, bk, bs, bi = (o[:, 0, 0] for o in outs)
    # lexicographic fold of the per-slab bests; ties keep the lowest index
    imax = jnp.iinfo(jnp.int32).max
    tie = bt == jnp.min(bt)
    kk = jnp.where(tie, bk, imax)
    tie = tie & (kk == jnp.min(kk))
    ss = jnp.where(tie, bs, imax)
    tie = tie & (ss == jnp.min(ss))
    best = jnp.min(jnp.where(tie, bi, imax))
    found = best != imax
    idx = jnp.where(found, jnp.minimum(best, max(q - 1, 0)), 0)
    return idx.astype(jnp.int32), found


def event_pop(time, kind, seq, valid, impl: Optional[str] = None,
              block_q: int = BLOCK_Q, interpret: Optional[bool] = None):
    """Queue-head selection with backend dispatch.

    ``impl``: "pallas" forces the kernel, "lax" the pure-lax oracle; None
    follows ``repro.kernels.dispatch`` (pallas on TPU, lax elsewhere).
    """
    if pick_impl(impl, "event_pop") == "lax":
        return ref.event_pop_ref(time, kind, seq, valid)
    return event_pop_pallas(time, kind, seq, valid,
                            block_q=block_q, interpret=interpret)
