"""Pallas kernel: fused per-receiver gossip-merge winner selection.

The anti-entropy hot spot (``repro.net.gossip``): every sync tick each of
the R nodes folds its active neighbors' DAG replicas into its own. The
row-wise merge rule (``repro.core.dag.merge``) is commutative/associative —
per ledger row the surviving transaction is the occupied candidate with the
lexicographically largest ``(publish_time, publisher)`` key, and the
``approval_count`` of that identity is the monotone max over every candidate
holding it — so the whole O(N) sender fold collapses into one masked
reduction over the sender axis (O(log N) depth, no N² ``DagState``
intermediates).

This module is ARRAY-level on purpose: it sees only the key/counter columns
``(publish_time, publisher, approval_count)`` plus the candidate mask, and
returns per-(receiver, row) winner *indices* — ``repro.core.dag.merge_select``
turns those into the merged ``DagState`` (payload gather + watermark max).
Keeping ``DagState`` out of this layer avoids an import cycle
(``repro.core.aggregation`` already imports ``repro.kernels.ops``).

Outputs, per receiver i and ledger row r (senders j masked by ``mask[i, j]``,
which INCLUDES the diagonal — the receiver itself is a candidate):

  src[i, r]   index j of the winning sender (i itself when the local row
              already holds the winning identity, or when no candidate is
              occupied — merge keeps the local row in both cases);
  ac[i, r]    max ``approval_count`` over candidates holding the winning
              identity (CRDT union-by-max; 0 when every candidate is empty,
              which is bitwise the empty row's counter).

Ties on the key prefer the receiver's own replica, then the lowest sender
index — exactly the order the PR-1 ``vmap``-over-``scan`` fold visited
candidates, so the fused round is bitwise-identical to it (tested by
``tests/test_gossip_merge.py``).

The kernel tiles (receivers x cap) — grid step (i, c) loads the (R, block_c)
key slab once and reduces it against receiver i's mask column. It runs
compiled on TPU and in the Pallas interpreter elsewhere
(``repro.kernels.dispatch``); ``repro.kernels.ref.gossip_winner_ref`` is
the pure-lax oracle that CPU paths route through.

Since the mesh-sharded round (PR 3), every entry point is BLOCK-addressed:
``mask`` may be a rectangular (Rr, R) receiver block of the full sender
axis — a shard reduces its own receivers against the all-gathered senders —
with the block's global position supplied as ``row_ids`` (per-receiver
sender ids, lax paths) or ``row_offset`` (contiguous block start, the
Pallas kernel's (1, 1) scalar input), so self-tie-preference and the
all-empty fallback keep addressing the receiver's own global row.
``row_ids=None`` / ``row_offset=0`` is the identity block (receiver i IS
sender i — the single-device round). ``repro.kernels.chunk_transfer`` is
the sibling reduction for bank gossip: chunk-availability dedup + transfer
selection in the same masked-reduction mold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.dispatch import interpret_mode, pick_impl

BLOCK_C = 256   # (R, 256) i32/f32 slabs x 3 inputs: ~300 KiB VMEM @ R=100


def _winner_kernel(off_ref, mask_ref, t_ref, p_ref, ac_ref, src_ref, ac_out_ref):
    # off_ref: (1, 1) i32 — global sender index of the block's receiver 0
    # mask_ref: (1, R, 1) i32 — receiver i's candidate column (self included)
    # t_ref/p_ref/ac_ref: (R, bc) — all senders' key/counter slabs
    # src_ref/ac_out_ref: (1, 1, bc) — winner index + merged counter for row i
    gid = off_ref[...] + pl.program_id(0)                    # (1, 1) global id
    r = t_ref.shape[0]
    m = mask_ref[0] != 0                                     # (R, 1)
    p = p_ref[...]
    valid = m & (p >= 0)                                     # occupied candidates
    tm = jnp.where(valid, t_ref[...], -jnp.inf)
    best_t = jnp.max(tm, axis=0, keepdims=True)              # (1, bc)
    tie = valid & (tm == best_t)
    pm = jnp.where(tie, p, jnp.iinfo(jnp.int32).min)
    best_p = jnp.max(pm, axis=0, keepdims=True)
    win = tie & (pm == best_p)                               # winning identity
    idx = jax.lax.broadcasted_iota(jnp.int32, win.shape, 0)
    first = jnp.min(jnp.where(win, idx, r), axis=0, keepdims=True)
    self_win = jnp.max(jnp.where(win & (idx == gid), 1, 0), axis=0,
                       keepdims=True) > 0
    src = jnp.where(self_win | (first >= r), gid, first)     # first>=r: all empty
    src_ref[0] = src.astype(jnp.int32)
    ac_out_ref[0] = jnp.max(jnp.where(win, ac_ref[...], 0), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def gossip_winner_pallas(
    publish_time: jnp.ndarray,    # (R, cap) f32
    publisher: jnp.ndarray,       # (R, cap) i32
    approval_count: jnp.ndarray,  # (R, cap) i32
    mask: jnp.ndarray,            # (Rr, R) bool — mask[i, j]: i hears j
    block_c: int = BLOCK_C,
    interpret: bool = None,
    row_offset=0,                 # () i32 — global sender index of receiver 0
) -> tuple:
    """(src, ac): per-row winner index and merged approval counter.

    ``mask`` may be a rectangular receiver block: a mesh shard
    (``repro.net.mesh``) computes its R/shards receivers against the
    all-gathered sender axis, passing the block's global start index as
    ``row_offset`` so self-tie-preference and the all-empty fallback keep
    addressing the receiver's own global row.

    Tiling: a ledger of ``cap <= block_c`` rows is one full-width column
    block; a wider one is cut into ``block_c``-row blocks (a multiple of
    the 128-lane tile) with empty padding rows (publisher -1, never a
    winner). The mask column and the per-receiver outputs travel as
    (Rr, R, 1) / (Rr, 1, cap) arrays, so every block's last two dims are
    the array's own — the TPU tiling rule for the receiver axis.
    """
    r, c = publish_time.shape
    rr = mask.shape[0]
    if c <= block_c:
        bc = max(c, 1)
    elif block_c % 128:
        raise ValueError(f"block_c={block_c} must be a multiple of 128 "
                         f"when it splits cap={c}")
    else:
        bc = block_c
    pad = (-c) % bc
    t = jnp.pad(publish_time, ((0, 0), (0, pad)))
    p = jnp.pad(publisher, ((0, 0), (0, pad)), constant_values=-1)
    ac = jnp.pad(approval_count, ((0, 0), (0, pad)))
    off = jnp.asarray(row_offset, jnp.int32)
    # the receiver is always a candidate (see ref.gossip_winner_ref)
    rows = jnp.arange(rr, dtype=jnp.int32)
    mask = jnp.asarray(mask).at[rows, off + rows].set(True)
    mask_col = mask.astype(jnp.int32)[:, :, None]            # (Rr, R, 1)

    src, ac_out = pl.pallas_call(
        _winner_kernel,
        grid=(rr, (c + pad) // bc),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, cb: (0, 0)),
            pl.BlockSpec((1, r, 1), lambda i, cb: (i, 0, 0)),
            pl.BlockSpec((r, bc), lambda i, cb: (0, cb)),
            pl.BlockSpec((r, bc), lambda i, cb: (0, cb)),
            pl.BlockSpec((r, bc), lambda i, cb: (0, cb)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bc), lambda i, cb: (i, 0, cb)),
            pl.BlockSpec((1, 1, bc), lambda i, cb: (i, 0, cb)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rr, 1, c + pad), jnp.int32),
            jax.ShapeDtypeStruct((rr, 1, c + pad), jnp.int32),
        ],
        interpret=interpret_mode(interpret),
    )(off.reshape(1, 1), mask_col, t, p, ac)
    return src[:, 0, :c], ac_out[:, 0, :c]


def gossip_winner_nbr(
    publish_time: jnp.ndarray,    # (R, cap) f32
    publisher: jnp.ndarray,       # (R, cap) i32
    approval_count: jnp.ndarray,  # (R, cap) i32
    nbr_idx: jnp.ndarray,         # (Rr, D) i32 candidate sender lists
    nbr_act: jnp.ndarray,         # (Rr, D) bool candidate activity
    row_ids: jnp.ndarray = None,  # (Rr,) i32 global sender index per receiver
) -> tuple:
    """Degree-compressed winner selection — the CPU/sparse-overlay fast path.

    Same rule as ``ref.gossip_winner_ref`` but candidates are gathered from
    per-receiver lists instead of masked out of the full sender axis:
    O(R * D * cap) work for max degree D instead of O(R^2 * cap), which is
    what makes the fused round beat the sequential fold on sparse overlays
    even on a single CPU core. ``nbr_idx`` rows may contain duplicates
    (padding); a receiver that should be its own candidate (always, in
    gossip) must appear in its list with ``nbr_act`` true. ``row_ids`` maps
    a rectangular receiver block to its global sender indices (a mesh shard
    reduces its own receivers against the gathered sender axis; None means
    receiver i is sender i). Equivalence with the dense oracle is
    property-tested.
    """
    r = publish_time.shape[0]
    t = publish_time[nbr_idx]                                # (Rr, D, cap)
    p = publisher[nbr_idx]
    a = approval_count[nbr_idx]
    valid = nbr_act[:, :, None] & (p >= 0)
    tm = jnp.where(valid, t, -jnp.inf)
    best_t = jnp.max(tm, axis=1)                             # (Rr, cap)
    tie = valid & (tm == best_t[:, None])
    pm = jnp.where(tie, p, jnp.iinfo(jnp.int32).min)
    best_p = jnp.max(pm, axis=1)
    win = tie & (pm == best_p[:, None])
    first = jnp.min(jnp.where(win, nbr_idx[:, :, None], r), axis=1)
    if row_ids is None:
        rows = jnp.arange(nbr_idx.shape[0], dtype=jnp.int32)[:, None]
        own_time, own_pub = publish_time, publisher
    else:
        rows = jnp.asarray(row_ids, jnp.int32)[:, None]
        own_time, own_pub = publish_time[rows[:, 0]], publisher[rows[:, 0]]
    self_act = jnp.any(nbr_act & (nbr_idx == rows), axis=1)
    self_win = (
        self_act[:, None]
        & (own_pub >= 0)
        & (own_time == best_t)
        & (own_pub == best_p)
    )
    src = jnp.where(self_win | (first >= r), rows, first)
    ac = jnp.max(jnp.where(win, a, 0), axis=1)
    return src.astype(jnp.int32), ac.astype(jnp.int32)


def gossip_winner(
    publish_time, publisher, approval_count, mask,
    impl: str = None, block_c: int = BLOCK_C, interpret: bool = None,
    row_offset=None,
):
    """Winner-selection reduction with backend dispatch.

    ``impl``: "pallas" forces the kernel, "lax" the pure-lax oracle; None
    follows ``repro.kernels.dispatch`` (pallas on TPU, lax elsewhere).
    ``row_offset`` (() i32) marks ``mask`` as a contiguous receiver
    block starting at that global sender index — the mesh-sharded round.
    """
    if pick_impl(impl, "gossip_winner") == "lax":
        row_ids = None
        if row_offset is not None:
            rr = mask.shape[0]
            row_ids = jnp.asarray(row_offset, jnp.int32) + jnp.arange(rr, dtype=jnp.int32)
        return ref.gossip_winner_ref(
            publish_time, publisher, approval_count, mask, row_ids=row_ids
        )
    return gossip_winner_pallas(
        publish_time, publisher, approval_count, mask,
        block_c=block_c, interpret=interpret,
        row_offset=0 if row_offset is None else row_offset,
    )
