"""Gossiped model bank: content-addressed chunks over a bandwidth budget.

The paper's DAG layer exchanges *models*, not just transaction metadata
(§III.A: each node's local DAG is "updated by communicating with adjacent
nodes"; Table I prices exactly that traffic at phi / B per transfer). Up to
PR 3 the simulator shared one host-side model bank, so a transaction's
payload was usable the instant its DAG row arrived — sync cost was free
where the paper says it dominates. This module makes payload transport a
first-class, *priced* part of the anti-entropy round while keeping the
payload bytes stored once:

  store          the model bank stays ONE content-addressed store (slot i of
                 every leaf is transaction i's model, `repro.core.bank`);
                 replicating N physical banks would multiply memory by N for
                 no informational gain. What is replicated per node is the
                 *presence bitmap*: which chunks of the store this node has
                 actually received.

  chunking       each bank slot is split into ``chunks_per_slot`` equal
                 byte ranges, identified by a content digest
                 (``chunk_digests`` — the per-chunk analogue of
                 ``bank.auth_checksum``). Chunking is ALIGNED: dedup
                 compares chunks at the same offset across slots, so an
                 identical payload (a lazy node republishing the aggregate
                 verbatim) costs zero bytes the second time, while
                 offset-shifted collisions are not modeled.

  transfer       every sync tick, after the DAG merge, each node derives
                 the chunks it still needs (rows visible in its replica
                 whose slots its effective availability — the
                 ``repro.kernels.chunk_transfer`` dedup reduction — does not
                 cover) and pulls them from active neighbors, charged
                 against a per-directed-link byte budget
                 ``bandwidth / 8 * sync_period`` (``Topology.bandwidth``,
                 Table-I B). Whole chunks transfer in canonical order;
                 partial-chunk budget ROLLS OVER across ticks (paused, not
                 lost, while a link is strided out or partitioned away), and
                 idle bandwidth is never banked.

  gating         a transaction is *usable* at a node only once its model
                 chunks have arrived: ``run_dagfl_gossip`` masks unavailable
                 rows out of the node's view (``gate_view``), so Algorithm-2
                 tip selection — and hence approvals — waits for the payload
                 exactly as BlockFL/DAG-AFL style delay analyses assume.

Infinite-bandwidth limit: with ``bandwidth=inf`` every assigned chunk
transfers on the tick its row arrives, availability tracks row visibility
exactly (induction from the committer, which holds its own chunks), and the
whole system is BITWISE the PR-3 path for every round impl — the transfer
step is deterministic and never touches the PRNG stream. Property-tested in
``tests/test_net_bank.py``.

Slot-reuse caveat: the ledger ring reuses slots, and the store always holds
a slot's *latest* content. A commit overwriting slot s resets every other
node's presence bits for s (they held the old content) and re-digests it;
a node still referencing the evicted row will re-fetch — and is gated on —
the new content until merge overwrites the stale row.

Wire compression (``BankGossipConfig.codec``,
``repro.kernels.delta_codec``): with a codec configured the FL driver
encodes every commit before it reaches the store — the store slot holds
the DEQUANTIZED wire values (so quantization error flows into training
exactly once, at commit), ``commit_chunks`` digests the ENCODED pytree
(the spoof defense verifies the bytes that actually cross the link), and
the engines scale ``chunk_bytes`` by ``codec.wire_ratio()`` so pricing,
the ``sent`` meter, and the event engine's drain instants all charge
encoded bytes. ``codec=None`` and the explicit identity codec keep every
jitted program LITERALLY the uncompressed one (``delta_codec.codec_key``),
the same contract the obs/faults static keys honor; pinned bitwise in
``tests/test_delta_codec.py``, formats in ``docs/WIRE_FORMAT.md``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.dag import DagState
from repro.kernels import chunk_transfer as ck
from repro.kernels import delta_codec as codec_lib


@dataclass(frozen=True)
class BankGossipConfig:
    """Knobs for gossiping the model bank.

    ``chunks_per_slot`` — byte ranges per bank slot (the transfer granule).
    ``slot_bytes`` — payload size per slot for pricing; None measures the
    actual bank leaves, while Table-I realism passes ``7e6`` (phi = 7 MB)
    so a bench-scale CNN is charged like the paper's model.
    ``impl`` — dedup reduction backend ("pallas" / "lax"; None auto-picks
    like ``kernels.chunk_transfer.chunk_dedup``).
    ``codec`` — wire compression for commits
    (``repro.kernels.delta_codec.DeltaCodec``); None ships raw f32 chunks
    and keeps the engines' jitted programs literally unchanged.
    """

    chunks_per_slot: int = 4
    slot_bytes: Optional[float] = None
    impl: Optional[str] = None
    codec: Optional["codec_lib.DeltaCodec"] = None


class BankState(NamedTuple):
    """Per-node bank-transport state (leading axis = replica, like ``dags``).

    ``have``   (R, S, C) bool — physical chunk presence per node;
    ``credit`` (R, R) f32 — rolled-over partial-chunk budget per directed
               link (receiver i <- sender j), bytes;
    ``sent``   (R, R) f32 — cumulative bytes delivered per directed link
               (the Table-I traffic the run actually paid for).
    """

    have: jnp.ndarray
    credit: jnp.ndarray
    sent: jnp.ndarray


def slot_nbytes(bank: Any) -> float:
    """Payload bytes of one bank slot (sum over leaves, sans the slot axis)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(bank):
        per = leaf.dtype.itemsize
        for d in leaf.shape[1:]:
            per *= d
        total += per
    return float(total)


def chunk_digests(params: Any, chunks: int) -> jnp.ndarray:
    """(chunks,) f32 content digests of one model payload.

    The payload is conceptually flattened leaf-by-leaf into one byte stream,
    split into ``chunks`` equal ranges, and each range is tagged with a
    fixed pseudo-random projection (the per-chunk analogue of
    ``bank.auth_checksum``): identical content → identical digest, any bit
    flip moves it. Deterministic and shape-independent given equal
    flattened values, which is all content addressing needs here.
    """
    leaves = [l.reshape(-1).astype(jnp.float32)
              for l in jax.tree_util.tree_leaves(params)]
    flat = jnp.concatenate(leaves) if len(leaves) > 1 else leaves[0]
    n = flat.shape[0]
    per = -(-n // chunks)                       # ceil; zero-pad the tail
    flat = jnp.pad(flat, (0, per * chunks - n)).reshape(chunks, per)
    idx = jnp.arange(per, dtype=jnp.float32)
    proj = jnp.cos(idx * 0.618033988749895) + 1e-3 * jnp.sin(idx * 0.318309886)
    return flat @ proj


def bank_digests(bank: Any, chunks: int) -> jnp.ndarray:
    """(S, chunks) f32 — digest table of the whole store (vmap over slots)."""
    return jax.vmap(lambda i: chunk_digests(
        jax.tree_util.tree_map(lambda b: b[i], bank), chunks
    ))(jnp.arange(jax.tree_util.tree_leaves(bank)[0].shape[0]))


def init_bank_state(num_replicas: int, slots: int, chunks: int) -> BankState:
    """Genesis transport state: every node already holds the initial store
    (all replicas start from the same fully-replicated view — the same
    assumption ``init_replicas`` makes for the ledger), no budget in flight,
    zero bytes on the meter."""
    return BankState(
        have=jnp.ones((num_replicas, slots, chunks), bool),
        credit=jnp.zeros((num_replicas, num_replicas), jnp.float32),
        sent=jnp.zeros((num_replicas, num_replicas), jnp.float32),
    )


def commit_chunks(have: jnp.ndarray, digest: jnp.ndarray, params: Any,
                  slot, node_id) -> tuple:
    """Account a stage-4 commit overwriting store ``slot`` with ``params``.

    The committer holds the new content; everyone else's presence bits for
    the slot are reset (they held the ring-evicted payload); the digest row
    is re-derived from the new bytes. ``params`` is only ever digested
    here, so a codec-enabled driver passes the ENCODED wire pytree — the
    digest table then addresses the bytes receivers actually pull.
    Returns ``(have, digest)``.
    """
    chunks = digest.shape[1]
    have = have.at[:, slot, :].set(False).at[node_id, slot, :].set(True)
    return have, digest.at[slot].set(chunk_digests(params, chunks))


# ---------------------------------------------------------------------------
# The per-tick transfer step (runs inside the jitted sync scan)
# ---------------------------------------------------------------------------


def referenced_slots(dags: DagState, slots: int) -> jnp.ndarray:
    """(R, S) bool — store slots referenced by rows visible in each replica."""
    r = dags.publisher.shape[0]
    occ = dags.publisher >= 0
    ms = jnp.maximum(dags.model_slot, 0)
    rows = jnp.arange(r, dtype=jnp.int32)[:, None]
    ref = jnp.zeros((r, slots), bool)
    return ref.at[rows, ms].max(occ)


def chunk_step(
    dags: DagState,            # receiver block's replicas (post-merge)
    bstate: BankState,         # receiver block's transport state
    digest: jnp.ndarray,       # (S, C) f32 store digest table (global)
    sat_all: jnp.ndarray,      # (R, S, C) bool EVERY sender's availability
    sat_blk: jnp.ndarray,      # (Rb, S, C) bool this block's availability
    edges: jnp.ndarray,        # (Rb, R) bool active directed edges
    cap_bytes: jnp.ndarray,    # (Rb, R) f32 per-link budget this tick
    chunk_bytes,               # () f32 transfer granule
    return_pending: bool = False,
):
    """One tick of priced chunk movement for a receiver block.

    Single-device calls pass the full axes (``sat_blk is sat_all``); a mesh
    shard passes its receiver block against the all-gathered availability
    bitmaps — never payloads (``gossip._shard_bank_tick``). Per-receiver
    arithmetic only, so both are bitwise-identical.

    ``return_pending=True`` additionally returns the (Rb, R) bool mask of
    links that still had assigned work after the budget ran out — the
    continuous-time event engine (``repro.net.events``) schedules a
    chunk-drain completion event from it; the default keeps the tick paths
    byte-for-byte what they were.
    """
    rb, s, c = sat_blk.shape
    ref = referenced_slots(dags, s)
    need = (ref[:, :, None] & ~sat_blk).reshape(rb, s * c)
    budget = bstate.credit + jnp.where(edges, cap_bytes, 0.0)
    afford = jnp.clip(
        jnp.floor(budget / chunk_bytes), 0, jnp.iinfo(jnp.int32).max
    ).astype(jnp.int32)
    take, spent_chunks, pending = ck.transfer_select(
        need, sat_all.reshape(-1, s * c), edges, afford
    )
    spent = spent_chunks.astype(jnp.float32) * chunk_bytes
    # rollover: keep residual while work is pending; pause (don't reset) on
    # links that did not fire; never bank idle bandwidth on an active link
    credit = jnp.where(pending, budget - spent,
                       jnp.where(edges, 0.0, bstate.credit))
    out = BankState(
        have=bstate.have | take.reshape(rb, s, c),
        credit=credit,
        sent=bstate.sent + spent,
    )
    if return_pending:
        return out, pending
    return out


# ---------------------------------------------------------------------------
# Availability views (gating + metrics)
# ---------------------------------------------------------------------------


def rows_available(dag: DagState, sat: jnp.ndarray) -> jnp.ndarray:
    """(..., cap) bool — rows whose model chunks have fully arrived.

    ``dag`` may be one replica with ``sat (S, C)`` or the stacked set with
    ``sat (R, S, C)``; empty rows count as available (there is nothing to
    wait for).
    """
    ms = jnp.maximum(dag.model_slot, 0)
    got = jnp.all(jnp.take_along_axis(
        sat, ms[..., None].astype(jnp.int32), axis=-2
    ), axis=-1)
    return (dag.publisher < 0) | got


def gate_view(dag: DagState, have_row: jnp.ndarray, digest: jnp.ndarray) -> DagState:
    """A node's USABLE view: rows whose payload has not arrived are masked
    to empty (publisher and model_slot -1), exactly as if the transaction
    had not been received — Algorithm 2 then neither selects nor approves
    it. With full availability this is the identity (bitwise), which is what
    keeps the infinite-bandwidth limit equal to the ungated PR-3 path.

    Stage-3 fallback caveat: when a node has NO usable tips it continues
    from its most recent *visible* model; masking ``model_slot`` makes a
    payload-less latest row fall back to the store's slot 0 rather than
    read bytes the node never received.
    """
    sat = ck.chunk_dedup(have_row[None], digest)[0]
    avail = rows_available(dag, sat)
    return dag._replace(
        publisher=jnp.where(avail, dag.publisher, -1),
        model_slot=jnp.where(avail, dag.model_slot, -1),
    )


def gate_views(dags: DagState, sat: jnp.ndarray) -> DagState:
    """All nodes' USABLE views at once: the stacked ``gate_view`` given a
    precomputed availability reduction ``sat (R, S, C)`` (the serve path
    already holds one from ``chunk_dedup`` — no re-reduction per node).
    Rows whose payload has not arrived mask to empty exactly as in
    ``gate_view``; with full availability this is the identity."""
    avail = rows_available(dags, sat)
    return dags._replace(
        publisher=jnp.where(avail, dags.publisher, -1),
        model_slot=jnp.where(avail, dags.model_slot, -1),
    )


def missing_chunks(dags: DagState, bstate: BankState,
                   digest: jnp.ndarray, impl: Optional[str] = None) -> jnp.ndarray:
    """(R,) int32 — referenced-but-unavailable chunks per node (0 = every
    visible transaction's model is locally usable)."""
    sat = ck.chunk_dedup(bstate.have, digest, impl=impl)
    ref = referenced_slots(dags, sat.shape[1])
    return jnp.sum((ref[:, :, None] & ~sat).astype(jnp.int32), axis=(1, 2))
