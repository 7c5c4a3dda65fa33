"""Pallas kernel: content-addressed chunk dedup + transfer selection.

The bank-gossip hot spot (``repro.net.bank``): every sync tick each node
must decide which model chunks it still needs (content-addressed dedup
against everything it already holds) and which of those its active
neighbors can supply within the tick's per-link byte budget. Both steps are
masked reductions in the same mold as ``repro.kernels.gossip_merge`` — no
data-dependent shapes, so the whole bank tick stays inside the jitted
``lax.scan`` of ``GossipNetwork.advance``.

Two layers, array-level on purpose (no ``DagState``/pytree types here):

``chunk_dedup``        sat[i, s, c] = node i effectively has chunk (s, c):
                       it physically holds some chunk (s', c) with an equal
                       content digest. Chunking is ALIGNED — dedup compares
                       chunks at the same offset c across slots, capturing
                       whole-model identity (lazy republish costs zero
                       bytes) but not offset-shifted collisions. Dense
                       blocked Pallas kernel (the TPU shape; interpreted
                       elsewhere) with ``repro.kernels.ref.chunk_dedup_ref``
                       as the pure-lax oracle/CPU fast path — the same
                       dispatch pattern as ``gossip_winner``.

``transfer_select``    per receiver, STRIPE the still-needed chunks across
                       the active neighbors that have the content (chunk m
                       goes to the (m mod holders)-th lowest-indexed active
                       holder, so parallel links to distinct holders drain
                       distinct chunks instead of idling behind the lowest
                       index), then admit chunks per link in canonical
                       (slot, chunk) order until the link's whole-chunk
                       budget runs out. Pure lax; deterministic (no
                       sampling), so the bank tick never touches the PRNG
                       stream and the gossip round stays bitwise-identical
                       with bank gossip enabled under infinite bandwidth.

Equivalence pallas-vs-ref is property-tested in ``tests/test_net_bank.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.dispatch import interpret_mode, pick_impl

BLOCK_S = 128   # digest slot-block per grid step


def _dedup_kernel(have_ref, dig_ref, dblk_ref, sat_ref):
    # have_ref: (1, S, C) i32 — receiver i's physical presence bitmap
    # dig_ref:  (S, C) f32   — full digest table (the dedup candidates)
    # dblk_ref: (bs, C) f32  — this block's target digests
    # sat_ref:  (1, bs, C) i32 — effective availability for the block
    hv = have_ref[...][0] != 0                               # (S, C)
    eq = dblk_ref[...][:, None, :] == dig_ref[...][None, :, :]   # (bs, S, C)
    sat = jnp.any(eq & hv[None, :, :], axis=1)               # (bs, C)
    sat_ref[...] = sat.astype(jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def chunk_dedup_pallas(
    have: jnp.ndarray,      # (R, S, C) bool
    digest: jnp.ndarray,    # (S, C) f32
    block_s: int = BLOCK_S,
    interpret: bool = None,
) -> jnp.ndarray:
    """(R, S, C) bool effective availability — the Pallas reduction.

    Grid step (i, sb) loads receiver i's presence bitmap once against a
    ``block_s``-slot slab of the digest table and any-reduces the aligned
    content matches. Padding slots carry NaN digests, which compare unequal
    to everything (including themselves), so they can neither satisfy nor
    be satisfied.
    """
    r, s, c = have.shape
    bs = min(block_s, s) if s else block_s
    pad = (-s) % bs
    dig = jnp.pad(jnp.asarray(digest, jnp.float32), ((0, pad), (0, 0)),
                  constant_values=jnp.nan)
    hv = jnp.pad(jnp.asarray(have, jnp.int32), ((0, 0), (0, pad), (0, 0)))

    sat = pl.pallas_call(
        _dedup_kernel,
        grid=(r, (s + pad) // bs),
        in_specs=[
            pl.BlockSpec((1, s + pad, c), lambda i, sb: (i, 0, 0)),
            pl.BlockSpec((s + pad, c), lambda i, sb: (0, 0)),
            pl.BlockSpec((bs, c), lambda i, sb: (sb, 0)),
        ],
        out_specs=pl.BlockSpec((1, bs, c), lambda i, sb: (i, sb, 0)),
        out_shape=jax.ShapeDtypeStruct((r, s + pad, c), jnp.int32),
        interpret=interpret_mode(interpret),
    )(hv, dig, dig)
    # physical presence short-circuits the digest match (NaN digests — a
    # payload that trained to NaN — compare unequal even to themselves;
    # see ref.chunk_dedup_ref)
    return (sat[:, :s, :] > 0) | jnp.asarray(have, bool)


def chunk_dedup(have, digest, impl: str = None, block_s: int = BLOCK_S,
                interpret: bool = None) -> jnp.ndarray:
    """Content-addressed availability with backend dispatch.

    ``impl``: "pallas" forces the kernel, "lax" the pure-lax oracle; None
    follows ``repro.kernels.dispatch`` (pallas on TPU, lax elsewhere).
    """
    if pick_impl(impl, "chunk_dedup") == "lax":
        return ref.chunk_dedup_ref(have, digest)
    return chunk_dedup_pallas(have, digest, block_s=block_s, interpret=interpret)


def transfer_select(
    need: jnp.ndarray,         # (Rb, M) bool — receiver block's wanted chunks
    src_have: jnp.ndarray,     # (R, M) bool — sender effective availability
    edge_active: jnp.ndarray,  # (Rb, R) bool — receiver i hears sender j
    afford: jnp.ndarray,       # (Rb, R) i32 — whole chunks per link this tick
    return_links: bool = False,
):
    """One tick of bandwidth-limited chunk transfers (pure lax, no PRNG).

    Needed chunks are STRIPED across the active senders whose effective
    availability covers them: chunk ``m`` is assigned to the
    ``(m mod holders)``-th lowest-indexed active holder, so when several
    neighbors hold the same content their links drain disjoint chunk sets
    in parallel instead of every chunk queueing behind the lowest-indexed
    holder. A single holder degenerates to exactly the lowest-index rule
    (deterministic — merge ties in the gossip round break the same way).
    Each link then admits its assigned chunks in ascending flat
    (slot, chunk) order until ``afford`` whole chunks have been spent.
    ``Rb`` may be a mesh shard's receiver block reduced against the
    all-gathered availability bitmaps — per-receiver arithmetic only, so
    the sharded tick is bitwise the single-device one.

    Returns ``(take (Rb, M) bool, spent (Rb, R) i32 chunks moved per link,
    pending (Rb, R) bool — link had assigned work left over)``. With
    ``return_links=True`` the per-link admission mask is exposed too:
    ``(take, take_link (Rb, R, M) bool, spent, pending)`` — the fault layer
    (``repro.net.faults``) needs sender attribution to verify digests and
    charge rejections per link; striping guarantees at most one sender per
    (receiver, chunk), so ``take == any(take_link, axis=1)`` loses nothing.
    """
    rb, m = need.shape
    r = src_have.shape[0]
    can = edge_active[:, :, None] & need[:, None, :] & src_have[None, :, :]
    # stripe: among a chunk's active holders (ranked by sender index), pick
    # the (chunk index mod holder count)-th — distinct chunks spread over
    # distinct links, and afford admission below stays per-link
    holder_rank = jnp.cumsum(can.astype(jnp.int32), axis=1) - 1   # (Rb, R, M)
    holders = jnp.sum(can.astype(jnp.int32), axis=1)              # (Rb, M)
    chunk_idx = jnp.arange(m, dtype=jnp.int32)[None, :]
    pick = jnp.where(
        holders > 0, jnp.mod(chunk_idx, jnp.maximum(holders, 1)), -1
    )
    assigned = can & (holder_rank == pick[:, None, :])            # (Rb, R, M)
    rank = jnp.cumsum(assigned.astype(jnp.int32), axis=2) - 1
    take_link = assigned & (rank < afford[:, :, None])
    take = jnp.any(take_link, axis=1)
    spent = jnp.sum(take_link.astype(jnp.int32), axis=2)
    pending = jnp.any(assigned & ~take_link, axis=2)
    if return_links:
        return take, take_link, spent, pending
    return take, spent, pending


def transfer_verify(
    take_link: jnp.ndarray,    # (Rb, R, M) bool — admitted transfers per link
    bad_link: jnp.ndarray,     # (Rb, R, M) bool — payload corrupted in flight
):
    """Digest check on receive: the defense-side reduction next to dedup.

    A receiver recomputes the content digest of every chunk it just pulled
    and compares against the digest table it already gossips
    (``repro.net.bank.chunk_digests``); a mismatch means the sender served
    bytes that do not hash to the announced content, so the chunk is
    dropped before it can satisfy ``need`` — it never reaches
    ``commit_chunks``/``gate_view``. Array form: ``bad_link`` marks the
    admitted transfers whose payload would fail that recomputation (spoofed
    in flight, or re-served from a tainted store).

    Returns ``(ok_take (Rb, M) bool — chunks that verified and may be
    committed, rejects (Rb, R) i32 — rejected chunk count charged to each
    (receiver, sender) link)``. With ``bad_link`` all-False this is bitwise
    ``(any(take_link, axis=1), zeros)`` — the honest path is unchanged.
    """
    rej = take_link & bad_link
    ok_take = jnp.any(take_link & ~bad_link, axis=1)
    return ok_take, jnp.sum(rej.astype(jnp.int32), axis=2)
