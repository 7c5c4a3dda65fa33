"""Anti-entropy gossip over the overlay: device-resident, tick-batched sync.

A sync tick folds every node's active neighbors into its local replica with
the ``dag.merge`` row rule. Two interchangeable round implementations:

  ``impl="fused"``   the fast path — per-row winner selection over ALL
                     senders in one masked reduction
                     (``repro.kernels.gossip_merge``; Pallas on TPU, its
                     pure-lax oracle elsewhere) followed by one payload
                     gather (``dag.merge_select``). O(log N) reduction
                     depth, no N² ``DagState`` intermediates.
  ``impl="scan"``    the PR-1 reference — ``vmap`` over receivers of a
                     ``lax.scan`` of sequential two-replica merges. Kept as
                     the bitwise ground truth (``tests/test_gossip_merge``)
                     and the benchmark baseline.

Dispatch batching: ``advance(t)`` no longer issues one jitted call per tick.
It precomputes the (tick index, partition-active) schedule for the whole
window host-side and runs ONE jitted ``lax.scan`` over it (PRNG keys split
inside the scan, so a batched window is bitwise the sequential ticks), and
``converge()`` runs the whole fixpoint iteration in ONE jitted
``lax.while_loop`` whose predicate (replicas synced / progress stalled) is
evaluated on device. Every state-advancing device call routes through the
``GossipNetwork._dispatch`` funnel — tick advance, event advance, the bank
variants, converge, and commit accounting alike — so ``device_calls`` is
the complete dispatch count benchmarks report (``dispatch_counts`` keeps
the per-entry-point breakdown). Each dispatch runs inside a
``jax.profiler.TraceAnnotation`` named ``repro.net.<label>``, and every
blocking device->host read the driver loop makes goes through the
``GossipNetwork._fetch`` funnel (``host_syncs``, ``sync_counts``, spans
``repro.net.wait.<label>``): with a profiler recording, the host's time
lands on the device trace's clock; without one, a span costs one check.
Replica reads (``read``, ``read_view``) are one compiled gather each
(``replica.read_replica``), counted apart in ``read_calls``.

Telemetry: constructed with ``obs_cfg=repro.obs.ObsConfig(...)``, the
jitted loops thread device-resident collectors (metric accumulators + an
event trace ring, ``repro.obs``) through their carries and the network
grows ``obs_report()`` / ``trace_host()``. Collection is a pure read —
same PRNG splits, bitwise-identical trajectory — and ``obs_cfg=None``
(the default) keeps every jitted program literally unchanged; both claims
are property-tested in ``tests/test_obs.py``.

Per-edge behavior (unchanged semantics):

  message loss   each directed message is dropped i.i.d. with the link's
                 drop probability (``Topology.drop``);
  link latency   a link with latency ℓ fires only every
                 ``ceil(ℓ / sync_period)`` ticks — slow links sync less
                 often (transfer time quantized to the tick grid);
  partitions     a ``PartitionSchedule`` suppresses cross-component edges
                 for t ∈ [t_start, t_end), then heals.

Mesh sharding: constructed with ``mesh=...`` (see ``repro.net.mesh``),
``GossipNetwork`` partitions the replica set's leading receiver axis over
the mesh's ``"nodes"`` axis and swaps the round body for a ``shard_map``:
each shard all-gathers the sender rows once (the round's one collective),
winner-reduces its own receiver block, and writes back only that block.
The tick-batched ``advance`` scan and the ``converge`` while-loop stay
device-resident and are traced once per (impl, mesh). ``mesh=None``
preserves the single-device paths bitwise, and the sharded round is
bitwise-equal to them (property-tested in ``tests/test_net_mesh.py``).

Bank gossip: constructed with ``bank_cfg=BankGossipConfig(...)``
(``repro.net.bank``), every tick also moves MODEL PAYLOAD availability:
after the row merge, each node pulls the content-addressed chunks of rows
it can see but cannot yet use, charged against the link's Table-I byte
budget (``Topology.bandwidth``; partial-chunk credit rolls over across
ticks). The transport state (presence bitmaps + link credit) rides the
same scan carry; under a mesh the tick all-gathers availability BITMAPS,
never payload bytes. The chunk step is deterministic — no PRNG — so with
unlimited capacity the whole trajectory is bitwise the ``bank_cfg=None``
path (the CI-enforced equivalence); ``converge()`` then also waits for
referenced chunks to arrive, with its tick bound extended by the slowest
link's slot-drain time. ``bank_cfg=None`` (default) is exactly the PR-3
driver. With ``bank_cfg.codec`` set (``repro.kernels.delta_codec``),
chunks are priced at their ENCODED byte size — the codec rides the jit
factories as another static key, and every ratio-1.0 codec maps to the
literal uncompressed program (``docs/WIRE_FORMAT.md``).

Continuous time: constructed with ``GossipConfig(engine="events")``,
``advance`` runs the ``repro.net.events`` engine instead of the tick scan —
per-edge deliveries fire at the link's ACTUAL latency (a 0.3 s link no
longer waits for the 1 s tick; a 3.7 s link is no longer rounded to 4),
and with the bank gossiped, chunk drains complete at whole-chunk instants
with continuously-accrued budget. ``engine="ticks"`` (the default) keeps
every path here bitwise what it was, and the degenerate uniform-delay
limit of the event engine is bitwise the tick path (CI-enforced; requires
a float32-exact period — see ``repro.net.events`` on the f32 event clock).
``converge()`` is the engine-independent anti-entropy fixpoint flush (the
tick while-loop — a flush has no timeline to quantize).

``GossipNetwork`` is the host-side driver the simulator talks to: it owns
the replica set, the tick clock, and the schedule bookkeeping; all jitted
entry points live at module level (cached per ``impl`` x ``mesh``
x bank backend), so constructing many networks in a benchmark sweep
re-traces nothing.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import dag as dag_lib
from repro.core.dag import DagState
from repro.kernels import chunk_transfer as chunk_kernel
from repro.kernels import delta_codec as codec_lib
from repro.kernels import gossip_merge as gossip_kernel
from repro.kernels.dispatch import pick_impl
from repro.net import bank as bank_lib
from repro.net import mesh as mesh_lib
from repro.net import replica as replica_lib
from repro.net.bank import BankGossipConfig, BankState
from repro.net.topology import Topology, neighbor_table, partition_matrix


@dataclass(frozen=True)
class PartitionSchedule:
    """Split the overlay into components for [t_start, t_end), then heal.

    ``assignment`` is an (N,) array of component labels; while active, only
    edges within a component deliver (§III.A under imperfect networks — the
    measurable question is how fast replicas reconverge after healing).
    """

    assignment: np.ndarray
    t_start: float
    t_end: float

    def active(self, t: float) -> bool:
        return self.t_start <= t < self.t_end


@dataclass(frozen=True)
class GossipConfig:
    """Anti-entropy knobs.

    ``sync_period <= 0`` means an ideal wire: every ``advance`` runs ticks
    until the replicas reach fixpoint — the shared-ledger limit used as the
    baseline (and by the acceptance test against ``run_dagfl``).
    ``max_ticks_per_advance`` bounds work when one advance window spans many
    periods; elided ticks are no-ops once the state has reached fixpoint
    (loss-free links), and with loss they only truncate redundant retries.
    ``impl`` picks the round implementation: "fused" (kernel reduction;
    Pallas on TPU, pure-lax elsewhere), "scan" (PR-1 reference fold), or the
    explicit backends "pallas" / "lax".

    ``engine`` picks the transport clock: "ticks" (the quantized stride
    model — every path bitwise what it was) or "events" (the continuous-time
    engine, ``repro.net.events``: per-edge deliveries at the link's actual
    latency, bank chunk-drains at whole-chunk completion instants, one
    jitted while_loop per advance). Under "events",
    ``max_ticks_per_advance`` caps how often each delivery edge fires per
    advance window — a backlog beyond the cap is ELIDED (the edge's
    schedule jumps past the window), bitwise the tick engine's
    fast-forward, so the degenerate-limit equivalence holds for any window
    size; ``max_events_per_advance`` bounds one dispatch's event batches,
    and a window truncated by it resumes on the next ``advance`` call.
    """

    sync_period: float = 1.0
    seed: int = 0
    max_ticks_per_advance: int = 64
    impl: str = "fused"
    engine: str = "ticks"
    max_events_per_advance: int = 8192


# ---------------------------------------------------------------------------
# Shared device-side pieces (module-level: traced once per impl, not per
# GossipNetwork instance)
# ---------------------------------------------------------------------------


def trees_equal(a, b) -> jnp.ndarray:
    """() bool — leaf-wise exact equality of two pytrees (same treedef).

    Shared by the converge fixpoint predicate and host-side stall checks;
    module-level so repeated ``GossipNetwork`` construction re-traces
    nothing.
    """
    flags = [
        jnp.all(x == y)
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    ]
    return jnp.all(jnp.stack(flags))


trees_equal_jit = jax.jit(trees_equal)


def _sample_edges(key, tick, part_mask, adj, drop, stride):
    """(N, N) bool active-edge mask for one tick."""
    live = adj & (jnp.mod(tick, stride) == 0) & part_mask
    u = jax.random.uniform(key, adj.shape)
    return live & (u >= drop)


@functools.lru_cache(maxsize=64)
def _neighbor_table_cached(mask_bytes: bytes, r: int):
    m = np.frombuffer(mask_bytes, bool).reshape(r, r)
    nbr_idx, nbr_valid = neighbor_table(m)
    return jnp.asarray(nbr_idx), jnp.asarray(nbr_valid)


def _round_scan(
    dags: DagState, edge_active: jnp.ndarray, senders: DagState = None
) -> DagState:
    """PR-1 reference round: vmap over receivers of a scan over senders.

    ``senders`` defaults to ``dags``; a mesh shard passes its local receiver
    block as ``dags`` and the all-gathered sender axis as ``senders``.
    """
    senders = dags if senders is None else senders

    def receive(dag_i, active_row):
        def body(carry, xs):
            dag_j, act = xs
            merged = dag_lib.merge(carry, dag_j)
            kept = jax.tree_util.tree_map(
                lambda m, c: jnp.where(act, m, c), merged, carry
            )
            return kept, None

        out, _ = jax.lax.scan(body, dag_i, (senders, active_row))
        return out

    return jax.vmap(receive)(dags, edge_active)


def _round_fused(
    dags: DagState, edge_active: jnp.ndarray,
    nbr_idx: jnp.ndarray, nbr_valid: jnp.ndarray, impl: str,
    senders: DagState = None, row_offset=None,
) -> DagState:
    """Fast path: one winner reduction + one payload gather per tick.

    "pallas" runs the dense blocked kernel over the full (receivers x cap)
    grid (the TPU shape; interpreted elsewhere); "lax" — the default off-TPU
    — gathers each receiver's candidate list and reduces over the max degree
    instead of the whole sender axis.

    THE round body, single-device and sharded alike: a mesh shard passes its
    receiver block as ``dags`` with the all-gathered sender axis as
    ``senders`` and the block's global start index as ``row_offset``
    (``edge_active``/``nbr_idx``/``nbr_valid`` then hold just the block's
    rows); the defaults are the identity block — every receiver, offset 0.
    """
    if impl == "fused":
        impl = pick_impl(None, "gossip round")
    senders = dags if senders is None else senders
    rb = dags.publisher.shape[0]
    rows = jnp.arange(rb, dtype=jnp.int32)
    if row_offset is not None:
        rows = rows + row_offset
    if impl == "pallas":
        # the receiver is a candidate
        mask = jnp.asarray(edge_active).at[jnp.arange(rb), rows].set(True)
        src, _ = gossip_kernel.gossip_winner_pallas(
            senders.publish_time, senders.publisher, senders.approval_count,
            mask, row_offset=0 if row_offset is None else row_offset,
        )
        return dag_lib.merge_select(senders, src, mask=mask)
    if impl != "lax":
        raise ValueError(f"unknown gossip round impl: {impl!r}")
    act = jnp.take_along_axis(edge_active, nbr_idx, axis=1) | (nbr_idx == rows[:, None])
    act = act & nbr_valid
    src, _ = gossip_kernel.gossip_winner_nbr(
        senders.publish_time, senders.publisher, senders.approval_count,
        nbr_idx, act, row_ids=None if row_offset is None else rows,
    )
    return dag_lib.merge_select(senders, src, nbr_idx=nbr_idx, nbr_act=act)


def _apply_round(
    dags: DagState, edge_active: jnp.ndarray,
    nbr_idx: jnp.ndarray, nbr_valid: jnp.ndarray, impl: str,
) -> DagState:
    if impl == "scan":
        return _round_scan(dags, edge_active)
    return _round_fused(dags, edge_active, nbr_idx, nbr_valid, impl)


@functools.lru_cache(maxsize=None)
def _round_jit(impl: str):
    return jax.jit(functools.partial(_apply_round, impl=impl))


# ---------------------------------------------------------------------------
# Mesh-sharded round: per-shard winner reduction + one collective row gather
# ---------------------------------------------------------------------------


def _shard_round_block(
    dags: DagState, edge_active: jnp.ndarray,
    nbr_idx: jnp.ndarray, nbr_valid: jnp.ndarray, impl: str,
) -> DagState:
    """One shard's share of a sync tick (runs under ``shard_map``).

    ``dags`` holds this shard's contiguous receiver block (R/shards rows of
    the stacked replica set); ``edge_active`` and the candidate table arrive
    replicated. The shard all-gathers the sender rows ONCE — the round's one
    collective; merge payload rows are small next to the model bank, which
    stays shared — then runs the SAME round body as the single-device path
    (``_round_fused``/``_round_scan``) restricted to its own receiver block
    (global ids ``off + arange``, so self-tie-preference and payload gathers
    keep addressing the gathered sender axis), and returns only its block.
    Bitwise-equal to the single-device round by construction: one shared
    body, identical candidate lists, masks, and reduction arithmetic per
    receiver row.
    """
    rb = dags.publisher.shape[0]
    off = jax.lax.axis_index(mesh_lib.NODES_AXIS) * rb
    senders = jax.tree_util.tree_map(
        lambda x: jax.lax.all_gather(x, mesh_lib.NODES_AXIS, axis=0, tiled=True),
        dags,
    )
    edges = jax.lax.dynamic_slice_in_dim(edge_active, off, rb, axis=0)
    if impl == "scan":
        return _round_scan(dags, edges, senders=senders)
    nbr = jax.lax.dynamic_slice_in_dim(nbr_idx, off, rb, axis=0)
    nbrv = jax.lax.dynamic_slice_in_dim(nbr_valid, off, rb, axis=0)
    return _round_fused(
        dags, edges, nbr, nbrv, impl, senders=senders, row_offset=off
    )


@functools.lru_cache(maxsize=None)
def _shard_round(impl: str, mesh):
    """shard_map'd round: receivers split over "nodes", everything else
    replicated (any extra mesh axes — e.g. "model" — replicate too)."""
    return jax.shard_map(
        functools.partial(_shard_round_block, impl=impl),
        mesh=mesh,
        in_specs=(P(mesh_lib.NODES_AXIS), P(), P(), P()),
        out_specs=P(mesh_lib.NODES_AXIS),
        check_vma=False,
    )


@functools.lru_cache(maxsize=None)
def _shard_round_jit(impl: str, mesh):
    return jax.jit(_shard_round(impl, mesh))


def _round_for(impl: str, mesh):
    """(dags, edges, nbr_idx, nbr_valid) -> dags round body per mesh.

    ``mesh=None`` returns the exact single-device body (today's behavior,
    bitwise); a mesh returns the shard_map'd round.
    """
    if mesh is None:
        return functools.partial(_apply_round, impl=impl)
    return _shard_round(impl, mesh)


# ---------------------------------------------------------------------------
# Bank-gossip tick: DAG round + priced chunk transfers (repro.net.bank)
# ---------------------------------------------------------------------------


def _bank_tick_single(dags, bstate, digest, edges, nbr_idx, nbr_valid,
                      cap_bytes, chunk_bytes, impl, bank_impl):
    """One sync tick with the model bank gossiped (single-device body).

    Rows merge first (the unchanged PR-3 round), then the chunk step runs on
    the POST-merge replicas over the SAME sampled edge mask: metadata and
    payload travel the same links in the same tick, so under infinite
    bandwidth availability tracks visibility exactly (see ``repro.net.bank``)
    and the dags trajectory — and the PRNG stream, which the deterministic
    chunk step never touches — is bitwise the bankless path.
    """
    dags = _apply_round(dags, edges, nbr_idx, nbr_valid, impl)
    sat = chunk_kernel.chunk_dedup(bstate.have, digest, impl=bank_impl)
    bstate = bank_lib.chunk_step(
        dags, bstate, digest, sat, sat, edges, cap_bytes, chunk_bytes
    )
    return dags, bstate


def _bank_tick_block(dags, have, credit, sent, digest, edges, nbr_idx,
                     nbr_valid, cap_bytes, chunk_bytes, impl, bank_impl):
    """One shard's share of a bank-gossip tick (runs under ``shard_map``).

    The DAG half is exactly ``_shard_round_block``; the bank half computes
    the dedup reduction for its own receiver block and ALL-GATHERS the
    resulting chunk-availability bitmaps — never payload bytes; the store
    stays shared — so its block's transfer selection sees every sender's
    effective availability, then updates only its block's presence/credit
    rows. Bitwise-equal to the single-device tick: per-receiver arithmetic
    over identical gathered operands.
    """
    rb = dags.publisher.shape[0]
    off = jax.lax.axis_index(mesh_lib.NODES_AXIS) * rb
    dags = _shard_round_block(dags, edges, nbr_idx, nbr_valid, impl)
    bstate = BankState(have=have, credit=credit, sent=sent)
    sat_blk = chunk_kernel.chunk_dedup(have, digest, impl=bank_impl)
    sat_all = jax.lax.all_gather(
        sat_blk, mesh_lib.NODES_AXIS, axis=0, tiled=True
    )
    edges_blk = jax.lax.dynamic_slice_in_dim(edges, off, rb, axis=0)
    cap_blk = jax.lax.dynamic_slice_in_dim(cap_bytes, off, rb, axis=0)
    bstate = bank_lib.chunk_step(
        dags, bstate, digest, sat_all, sat_blk, edges_blk, cap_blk, chunk_bytes
    )
    return dags, bstate.have, bstate.credit, bstate.sent


@functools.lru_cache(maxsize=None)
def _shard_bank_tick(impl: str, bank_impl, mesh):
    p_nodes, p_rep = P(mesh_lib.NODES_AXIS), P()
    return jax.shard_map(
        functools.partial(_bank_tick_block, impl=impl, bank_impl=bank_impl),
        mesh=mesh,
        in_specs=(p_nodes, p_nodes, p_nodes, p_nodes,
                  p_rep, p_rep, p_rep, p_rep, p_rep, p_rep),
        out_specs=(p_nodes, p_nodes, p_nodes, p_nodes),
        check_vma=False,
    )


def _bank_tick_for(impl: str, bank_impl, mesh):
    """(dags, bstate, digest, edges, nbr_idx, nbr_valid, cap, chunk_bytes)
    -> (dags, bstate) tick body; ``mesh=None`` is the single-device tick,
    a mesh routes both halves through one ``shard_map``."""
    if mesh is None:
        return functools.partial(
            _bank_tick_single, impl=impl, bank_impl=bank_impl
        )
    tick = _shard_bank_tick(impl, bank_impl, mesh)

    def run(dags, bstate, digest, edges, nbr_idx, nbr_valid, cap_bytes,
            chunk_bytes):
        dags, have, credit, sent = tick(
            dags, bstate.have, bstate.credit, bstate.sent, digest, edges,
            nbr_idx, nbr_valid, cap_bytes, chunk_bytes,
        )
        return dags, BankState(have=have, credit=credit, sent=sent)

    return run


def _codec_tick(tick, codec):
    """Wrap a bank tick body so every consumer of ``chunk_bytes`` — credit
    pricing, the ``sent`` meter, afford — is charged the codec's ENCODED
    byte size. ``codec=None`` (the ``delta_codec.codec_key`` image of every
    ratio-1.0 codec) returns the tick body UNTOUCHED, so the identity path
    stays the literal uncompressed program."""
    if codec is None:
        return tick
    ratio = codec.wire_ratio()

    def run(dags, bstate, digest, edges, nbr_idx, nbr_valid, cap_bytes,
            chunk_bytes):
        return tick(dags, bstate, digest, edges, nbr_idx, nbr_valid,
                    cap_bytes, chunk_bytes * ratio)

    return run


def _observer(obs, mesh=None, bank_impl=None):
    """``obs_lib.observe_round`` for the tick loops, ``obs`` and
    ``bank_impl`` bound. Under a mesh it runs replicated
    (``mesh_lib.replicated``): the telemetry reaches kernels (the union
    fold, chunk accounting, histogram bincount) that XLA cannot partition
    over the sharded receiver axis."""
    from repro import obs as obs_lib   # deferred: repro.obs imports repro.net

    def observe(metrics, ring, t, old, new, live_edges, bytes_delta=None,
                bstate=None, digest=None, old_have=None):
        return obs_lib.observe_round(
            obs, metrics, ring, t, old, new, live_edges=live_edges,
            bytes_delta=bytes_delta, bstate=bstate, digest=digest,
            bank_impl=bank_impl, old_have=old_have,
        )

    return observe if mesh is None else mesh_lib.replicated(observe, mesh)


@functools.lru_cache(maxsize=None)
def _advance_bank_jit(impl: str, bank_impl, mesh=None, obs=None, faults=None,
                      codec=None):
    """Tick-batched advance with the bank gossiped: the same ONE-``lax.scan``
    window as ``_advance_jit`` — same PRNG splits, same edge samples — with
    the transport state threaded through the carry. ``obs`` threads the
    telemetry carry too (``obs=None`` keeps the untouched program); the
    bank run additionally samples chunk lag / byte totals and records a
    DRAIN trace span per link that moved payload. ``faults`` (a
    ``repro.net.faults.FaultConfig``) swaps in the fault-injected body —
    ``faults=None`` keeps the untouched program below. ``codec`` (pre-mapped
    through ``delta_codec.codec_key``) scales ``chunk_bytes`` to the
    encoded wire size inside the body; ``codec=None`` keeps the literal
    raw-chunk program."""
    if faults is not None:
        from repro.net import faults as faults_lib   # deferred: faults imports this module
        return faults_lib._advance_bank_faults_jit(impl, bank_impl, faults,
                                                   obs, codec)
    tick = _codec_tick(_bank_tick_for(impl, bank_impl, mesh), codec)

    if obs is None:
        def advance(dags, bstate, digest, key, ticks, part_active, adj, drop,
                    stride, part_mask, nbr_idx, nbr_valid, cap_bytes,
                    chunk_bytes):
            def body(carry, xs):
                dags, bstate, key = carry
                tick_i, pact = xs
                key, sub = jax.random.split(key)
                pm = jnp.where(pact, part_mask, True)
                edges = _sample_edges(sub, tick_i, pm, adj, drop, stride)
                dags, bstate = tick(dags, bstate, digest, edges, nbr_idx,
                                    nbr_valid, cap_bytes, chunk_bytes)
                return (dags, bstate, key), None

            (dags, bstate, key), _ = jax.lax.scan(
                body, (dags, bstate, key), (ticks, part_active)
            )
            return dags, bstate, key

        return jax.jit(advance)

    observe = _observer(obs, mesh, bank_impl)

    def advance(dags, bstate, digest, key, ticks, part_active, adj, drop,
                stride, part_mask, nbr_idx, nbr_valid, cap_bytes, chunk_bytes,
                metrics, ring, period):
        def body(carry, xs):
            dags, bstate, key, metrics, ring = carry
            tick_i, pact = xs
            key, sub = jax.random.split(key)
            pm = jnp.where(pact, part_mask, True)
            edges = _sample_edges(sub, tick_i, pm, adj, drop, stride)
            new, newb = tick(dags, bstate, digest, edges, nbr_idx,
                             nbr_valid, cap_bytes, chunk_bytes)
            t = (tick_i.astype(jnp.float32) + 1.0) * period
            metrics, ring = observe(metrics, ring, t, dags, new, edges,
                                    newb.sent - bstate.sent, newb, digest,
                                    bstate.have)
            return (new, newb, key, metrics, ring), None

        (dags, bstate, key, metrics, ring), _ = jax.lax.scan(
            body, (dags, bstate, key, metrics, ring), (ticks, part_active)
        )
        return dags, bstate, key, metrics, ring

    return jax.jit(advance)


@functools.lru_cache(maxsize=None)
def _converge_bank_jit(impl: str, bank_impl, mesh=None, obs=None, faults=None,
                       codec=None):
    """Fixpoint flush with the bank gossiped: one ``lax.while_loop`` whose
    predicate also demands every replica's referenced chunks have ARRIVED —
    rows synced is no longer enough when payloads lag — and whose stall
    check watches the transport state too (credit accrual on a pending link
    is progress; a full stride cycle with nothing moving is a fixpoint).
    ``obs`` threads the telemetry carry (``obs=None`` keeps the untouched
    program); ``faults`` swaps in the fault-injected body (``faults=None``
    keeps the untouched program below); ``codec`` prices chunks at encoded
    bytes (``codec=None`` keeps the literal raw-chunk program)."""
    if faults is not None:
        from repro.net import faults as faults_lib
        return faults_lib._converge_bank_faults_jit(impl, bank_impl, faults,
                                                    obs, codec)
    tick = _codec_tick(_bank_tick_for(impl, bank_impl, mesh), codec)

    def synced(dags, bstate, digest):
        return replica_lib.replicas_synced(dags) & (
            jnp.max(bank_lib.missing_chunks(dags, bstate, digest,
                                            impl=bank_impl)) == 0
        )

    if obs is None:
        def converge(dags, bstate, digest, key, tick0, part_mask, adj, drop,
                     stride, limit, stall_limit, nbr_idx, nbr_valid,
                     cap_bytes, chunk_bytes):
            def cond(carry):
                dags, bstate, _key, _tick, stalled, done = carry
                return (
                    ~synced(dags, bstate, digest)
                    & (done < limit)
                    & (stalled < stall_limit)
                )

            def body(carry):
                dags, bstate, key, tick_i, stalled, done = carry
                key, sub = jax.random.split(key)
                edges = _sample_edges(sub, tick_i, part_mask, adj, drop, stride)
                new, newb = tick(dags, bstate, digest, edges, nbr_idx,
                                 nbr_valid, cap_bytes, chunk_bytes)
                still = trees_equal((new, newb), (dags, bstate))
                stalled = jnp.where(still, stalled + 1, 0)
                return (new, newb, key, tick_i + 1, stalled, done + 1)

            dags, bstate, key, tick_i, _, done = jax.lax.while_loop(
                cond, body,
                (dags, bstate, key, tick0, jnp.int32(0), jnp.int32(0)),
            )
            return (dags, bstate, key, tick_i, done,
                    synced(dags, bstate, digest))

        return jax.jit(converge)

    observe = _observer(obs, mesh, bank_impl)

    def converge(dags, bstate, digest, key, tick0, part_mask, adj, drop,
                 stride, limit, stall_limit, nbr_idx, nbr_valid, cap_bytes,
                 chunk_bytes, metrics, ring, period):
        def cond(carry):
            dags, bstate, _key, _tick, stalled, done = carry[:6]
            return (
                ~synced(dags, bstate, digest)
                & (done < limit)
                & (stalled < stall_limit)
            )

        def body(carry):
            dags, bstate, key, tick_i, stalled, done, metrics, ring = carry
            key, sub = jax.random.split(key)
            edges = _sample_edges(sub, tick_i, part_mask, adj, drop, stride)
            new, newb = tick(dags, bstate, digest, edges, nbr_idx, nbr_valid,
                             cap_bytes, chunk_bytes)
            still = trees_equal((new, newb), (dags, bstate))
            stalled = jnp.where(still, stalled + 1, 0)
            t = (tick_i.astype(jnp.float32) + 1.0) * period
            metrics, ring = observe(metrics, ring, t, dags, new, edges,
                                    newb.sent - bstate.sent, newb, digest,
                                    bstate.have)
            return (new, newb, key, tick_i + 1, stalled, done + 1,
                    metrics, ring)

        dags, bstate, key, tick_i, _, done, metrics, ring = (
            jax.lax.while_loop(
                cond, body,
                (dags, bstate, key, tick0, jnp.int32(0), jnp.int32(0),
                 metrics, ring),
            )
        )
        return (dags, bstate, key, tick_i, done,
                synced(dags, bstate, digest), metrics, ring)

    return jax.jit(converge)


def make_gossip_round(impl: str = "fused", mesh=None):
    """(dags, edge_active) -> dags anti-entropy round (one jitted call).

    ``edge_active[i, j]`` = receiver i hears sender j this tick. Merge is
    commutative/associative, so folding senders in index order is as good as
    any delivery order — which is also why the non-"scan" impls may replace
    the fold with a masked winner reduction (bitwise-equal, tested). The
    fused impls derive the candidate table from the concrete ``edge_active``
    (cached), so this entry point wants concrete masks; jitted drivers
    (``GossipNetwork``) precompute the table from the static adjacency
    instead. With ``mesh`` the stacked replicas are placed receiver-sharded
    and the round runs as the shard_map body (``_shard_round``).
    """
    if mesh is None:
        if impl == "scan":
            round_scan = _round_jit(impl)
            return lambda dags, edge_active: round_scan(
                dags, edge_active, None, None
            )

        def round_fn(dags, edge_active):
            m = np.asarray(edge_active, bool)
            nbr_idx, nbr_valid = _neighbor_table_cached(m.tobytes(), m.shape[0])
            return _round_jit(impl)(dags, edge_active, nbr_idx, nbr_valid)

        return round_fn

    def round_fn(dags, edge_active):
        m = np.asarray(edge_active, bool)
        mesh_lib.validate_replica_mesh(m.shape[0], mesh)
        nbr_idx, nbr_valid = _neighbor_table_cached(m.tobytes(), m.shape[0])
        dags = mesh_lib.shard_replicas(dags, mesh)
        return _shard_round_jit(impl, mesh)(
            dags, jnp.asarray(m), nbr_idx, nbr_valid
        )

    return round_fn


@functools.lru_cache(maxsize=None)
def _advance_jit(impl: str, mesh=None, obs=None, faults=None):
    """One jitted lax.scan running a whole advance window of sync ticks.

    The PRNG key is split inside the scan exactly like the sequential
    per-tick path did host-side, so a batched window is bitwise-identical to
    running its ticks one call at a time. Retraces once per distinct window
    length (a handful of lengths occur in practice) and once per mesh shape
    — under a mesh the scan body routes through the shard_map'd round
    (edge sampling stays a replicated global computation, so the sampled
    masks are bitwise the single-device ones).

    ``obs`` (an ``repro.obs.ObsConfig``) threads the telemetry collectors
    through the scan carry — a pure read sampled after each round, so the
    dags/key trajectory is bitwise the ``obs=None`` program, whose body
    below is literally the untouched code. ``faults`` (a
    ``repro.net.faults.FaultConfig``) swaps in the fault-injected body —
    ``faults=None`` keeps the untouched program below.
    """
    if faults is not None:
        from repro.net import faults as faults_lib
        return faults_lib._advance_faults_jit(impl, faults, obs)
    apply_round = _round_for(impl, mesh)

    if obs is None:
        def advance(dags, key, ticks, part_active, adj, drop, stride,
                    part_mask, nbr_idx, nbr_valid):
            def body(carry, xs):
                dags, key = carry
                tick, pact = xs
                key, sub = jax.random.split(key)
                pm = jnp.where(pact, part_mask, True)
                edges = _sample_edges(sub, tick, pm, adj, drop, stride)
                return (apply_round(dags, edges, nbr_idx, nbr_valid), key), None

            (dags, key), _ = jax.lax.scan(
                body, (dags, key), (ticks, part_active)
            )
            return dags, key

        return jax.jit(advance)

    observe = _observer(obs, mesh)

    def advance(dags, key, ticks, part_active, adj, drop, stride, part_mask,
                nbr_idx, nbr_valid, metrics, ring, period):
        def body(carry, xs):
            dags, key, metrics, ring = carry
            tick, pact = xs
            key, sub = jax.random.split(key)
            pm = jnp.where(pact, part_mask, True)
            edges = _sample_edges(sub, tick, pm, adj, drop, stride)
            new = apply_round(dags, edges, nbr_idx, nbr_valid)
            t = (tick.astype(jnp.float32) + 1.0) * period
            metrics, ring = observe(metrics, ring, t, dags, new, edges)
            return (new, key, metrics, ring), None

        (dags, key, metrics, ring), _ = jax.lax.scan(
            body, (dags, key, metrics, ring), (ticks, part_active)
        )
        return dags, key, metrics, ring

    return jax.jit(advance)


@functools.lru_cache(maxsize=None)
def _converge_jit(impl: str, mesh=None, obs=None, faults=None):
    """Device-resident fixpoint flush: ONE jitted lax.while_loop.

    The predicate — not yet synced, tick budget left, progress not stalled
    for a full stride cycle — runs on device, replacing the host loop that
    dispatched a sync round, an equality check, and a synced check per tick.
    Under a mesh the loop body routes through the shard_map'd round; the
    predicate's reductions are global (GSPMD inserts the collectives).
    ``obs`` threads the telemetry carry exactly as in ``_advance_jit``
    (``obs=None`` keeps the untouched program; a flush has no timeline, so
    its samples sit at the tick arithmetic's ``(tick + 1) * period``).
    ``faults`` swaps in the fault-injected body (``faults=None`` keeps the
    untouched program below).
    """
    if faults is not None:
        from repro.net import faults as faults_lib
        return faults_lib._converge_faults_jit(impl, faults, obs)
    apply_round = _round_for(impl, mesh)

    if obs is None:
        def converge(dags, key, tick, part_mask, adj, drop, stride, limit,
                     stall_limit, nbr_idx, nbr_valid):
            def cond(carry):
                dags, _key, _tick, stalled, done = carry
                return (
                    ~replica_lib.replicas_synced(dags)
                    & (done < limit)
                    & (stalled < stall_limit)
                )

            def body(carry):
                dags, key, tick, stalled, done = carry
                key, sub = jax.random.split(key)
                edges = _sample_edges(sub, tick, part_mask, adj, drop, stride)
                new = apply_round(dags, edges, nbr_idx, nbr_valid)
                stalled = jnp.where(trees_equal(new, dags), stalled + 1, 0)
                return (new, key, tick + 1, stalled, done + 1)

            dags, key, tick, _, done = jax.lax.while_loop(
                cond, body,
                (dags, key, tick, jnp.int32(0), jnp.int32(0)),
            )
            return dags, key, tick, done, replica_lib.replicas_synced(dags)

        return jax.jit(converge)

    observe = _observer(obs, mesh)

    def converge(dags, key, tick, part_mask, adj, drop, stride, limit,
                 stall_limit, nbr_idx, nbr_valid, metrics, ring, period):
        def cond(carry):
            dags, _key, _tick, stalled, done = carry[:5]
            return (
                ~replica_lib.replicas_synced(dags)
                & (done < limit)
                & (stalled < stall_limit)
            )

        def body(carry):
            dags, key, tick, stalled, done, metrics, ring = carry
            key, sub = jax.random.split(key)
            edges = _sample_edges(sub, tick, part_mask, adj, drop, stride)
            new = apply_round(dags, edges, nbr_idx, nbr_valid)
            stalled = jnp.where(trees_equal(new, dags), stalled + 1, 0)
            t = (tick.astype(jnp.float32) + 1.0) * period
            metrics, ring = observe(metrics, ring, t, dags, new, edges)
            return (new, key, tick + 1, stalled, done + 1, metrics, ring)

        dags, key, tick, _, done, metrics, ring = jax.lax.while_loop(
            cond, body,
            (dags, key, tick, jnp.int32(0), jnp.int32(0), metrics, ring),
        )
        return (dags, key, tick, done, replica_lib.replicas_synced(dags),
                metrics, ring)

    return jax.jit(converge)


# commit accounting shares one trace across every network instance
_bank_commit_jit = jax.jit(bank_lib.commit_chunks)


@functools.lru_cache(maxsize=None)
def _trace_one_jit(n: int):
    """Jitted single-record append into the device trace ring.

    The record (t, kind, src, dst, arg) goes through the SAME
    ``TraceRing.append_edges`` prefix-sum path the in-loop collectors use
    — a one-hot (N, N) mask in the [receiver, sender] layout selects the
    slot — so host-initiated spans (PUBLISH/COMMIT under
    ``ObsConfig.device_spans``) share the ring's capacity/overflow
    discipline with the device-recorded kinds.
    """
    def append(ring, t, kind, src, dst, arg):
        from repro.obs import trace as obs_trace
        ids = jnp.arange(n, dtype=jnp.int32)
        mask = (ids[:, None] == dst) & (ids[None, :] == src)
        return obs_trace.append_edges(ring, t, kind, mask, arg)

    return jax.jit(append)


def stride_matrix(top: Topology, sync_period: float, use_strides: bool = True) -> np.ndarray:
    """(N, N) int32 tick stride per link: a link with latency ℓ fires every
    ``ceil(ℓ / sync_period)`` ticks. ``use_strides=False`` (the ideal wire,
    ``sync_period <= 0``) delivers on every tick regardless of latency.
    Clipped to 2**30 so pathological latency/period ratios stay int32-safe
    (such links effectively never fire instead of overflowing to garbage)."""
    n = top.num_nodes
    if not use_strides:
        return np.ones((n, n), np.int32)
    period = max(float(sync_period), 1e-9)
    finite_lat = np.where(np.isfinite(top.latency), top.latency, 0.0)
    stride = np.where(
        top.adjacency, np.maximum(1.0, np.ceil(finite_lat / period)), 1.0
    )
    return np.minimum(stride, 2.0 ** 30).astype(np.int32)


class GossipNetwork:
    """Host-side overlay driver: replicas + tick clock + schedule batching."""

    def __init__(
        self,
        dag: DagState,
        bank: Any,
        top: Topology,
        cfg: GossipConfig = GossipConfig(),
        partition: Optional[PartitionSchedule] = None,
        mesh=None,
        bank_cfg: Optional[BankGossipConfig] = None,
        obs_cfg=None,
        faults_cfg=None,
        serve_cfg=None,
    ):
        n = top.num_nodes
        self.topology = top
        self.cfg = cfg
        self.partition = partition
        self.mesh = mesh
        self.bank_cfg = bank_cfg
        self.obs_cfg = obs_cfg
        self.faults_cfg = faults_cfg
        self._fstate = None
        if faults_cfg is not None:
            from repro.net import faults as faults_lib
            if mesh is not None:
                raise NotImplementedError(
                    "fault injection is single-device for now — the role "
                    "masks and FaultState are not mesh-sharded (see ROADMAP "
                    "open items)"
                )
            faults_lib.validate_faults(faults_cfg, n, bank=bank_cfg is not None)
        # init_replicas validates the mesh and shards the receiver axis
        self.replicas = replica_lib.init_replicas(dag, bank, n, mesh=mesh)
        if bank_cfg is not None:
            c = bank_cfg.chunks_per_slot
            slots = jax.tree_util.tree_leaves(bank)[0].shape[0]
            slot_b = (bank_lib.slot_nbytes(bank) if bank_cfg.slot_bytes is None
                      else float(bank_cfg.slot_bytes))
            self._chunk_bytes = jnp.float32(max(slot_b / c, 1e-9))
            # the static codec key for the bank jit factories: None for
            # every codec that prices like raw bytes, so the identity
            # path keeps the literal uncompressed programs
            self._codec = codec_lib.codec_key(bank_cfg.codec)
            self._digest = jax.jit(
                bank_lib.bank_digests, static_argnames="chunks"
            )(bank, chunks=c)
            bstate = bank_lib.init_bank_state(n, slots, c)
            # per-tick, per-directed-link byte budget: Table-I bits/s over
            # one sync period. sync_period <= 0 is the ideal wire — payload
            # transport is as free as metadata there, whatever `bandwidth`
            # says (the PR-3 limit the equivalence tests pin).
            if cfg.sync_period > 0:
                cap = top.bandwidth / 8.0 * cfg.sync_period
            else:
                cap = np.where(top.adjacency, np.inf, 0.0)
            # converge()'s tick bound must also cover DRAINING payloads: a
            # full slot over the slowest finite link costs this many ticks
            # (0 when every link is ideal or dead — rows alone bound those)
            finite = cap[top.adjacency & np.isfinite(cap) & (cap > 0)]
            self._drain_ticks = (
                int(min(np.ceil(slot_b / float(finite.min())), 256))
                if finite.size else 0
            )
            self._cap_bytes = jnp.asarray(cap, jnp.float32)
            if mesh is not None:
                bstate = mesh_lib.shard_replicas(bstate, mesh)
                self._digest, self._cap_bytes = (
                    mesh_lib.replicate(x, mesh)
                    for x in (self._digest, self._cap_bytes)
                )
            self.replicas = self.replicas._replace(bank_state=bstate)
            if faults_cfg is not None:
                from repro.net import faults as faults_lib
                self._fstate = faults_lib.init_fault_state(n, slots, c)
        stride = stride_matrix(top, cfg.sync_period, use_strides=cfg.sync_period > 0)
        self._max_stride = (
            int(stride[top.adjacency].max()) if top.adjacency.any() else 1
        )
        self._adj = jnp.asarray(top.adjacency)
        self._drop = jnp.asarray(top.drop)
        self._stride = jnp.asarray(stride)
        nbr_idx, nbr_valid = neighbor_table(top.adjacency)
        self._nbr_idx = jnp.asarray(nbr_idx)
        self._nbr_valid = jnp.asarray(nbr_valid)
        self._key = jax.random.PRNGKey(cfg.seed)
        self._all_mask = jnp.ones((n, n), bool)
        self._part_mask = (
            jnp.asarray(partition_matrix(partition.assignment))
            if partition is not None else self._all_mask
        )
        if mesh is not None:
            # overlay-wide arrays replicated so the jitted loops see one
            # committed layout per mesh (the replicas are receiver-sharded
            # by init_replicas above)
            (self._adj, self._drop, self._stride, self._nbr_idx,
             self._nbr_valid, self._all_mask, self._part_mask) = (
                mesh_lib.replicate(x, mesh) for x in (
                    self._adj, self._drop, self._stride, self._nbr_idx,
                    self._nbr_valid, self._all_mask, self._part_mask,
                )
            )
        self.tick = 0                # global tick index (drives strides)
        self.rounds_run = 0          # ticks / event batches actually executed
        self.device_calls = 0        # jitted dispatches issued (_dispatch)
        self.dispatch_counts = {}    # per-entry-point dispatch breakdown
        self.host_syncs = 0          # blocking device->host reads (_fetch)
        self.sync_counts = {}        # per-label read breakdown
        self.read_calls = 0          # compiled replica reads (read, read_view)
        self.events_processed = 0    # event batches fired (engine="events")
        if obs_cfg is not None:
            # telemetry carries (repro.obs): device-resident, threaded
            # through every jitted loop below as pure reads
            from repro import obs as obs_lib
            self._metrics = obs_lib.init_metrics(n, obs_cfg)
            self._ring = obs_lib.init_trace(obs_cfg.trace_capacity)
            self._obs_period = jnp.float32(max(cfg.sync_period, 0.0))
            self._host_events = []        # (t, kind, src, dst, arg) spans
            self._part_logged = [False, False]
            if mesh is not None:
                self._metrics = mesh_lib.replicate(self._metrics, mesh)
                self._ring = mesh_lib.replicate(self._ring, mesh)
        period = cfg.sync_period
        # wall-clock sample instant per tick — (tick + 1) * period, the
        # telemetry convention; the fault layer's crash windows use it too
        self._period = jnp.float32(max(period, 0.0))
        self._next_tick_t = period if period > 0 else 0.0
        if cfg.engine not in ("ticks", "events"):
            raise ValueError(f"unknown gossip engine: {cfg.engine!r}")
        if cfg.engine == "events":
            if mesh is not None:
                raise NotImplementedError(
                    "engine='events' is single-device for now — the event "
                    "queue is not mesh-sharded (see ROADMAP open items)"
                )
            from repro.net import events as events_lib
            self._equeue, self._eislot = events_lib.make_edge_queue(
                top, period if period > 0 else 1.0,
                drain_slots=bank_cfg is not None,
            )
            if partition is not None:
                self._part_t0 = jnp.float32(partition.t_start)
                self._part_t1 = jnp.float32(partition.t_end)
            else:
                self._part_t0 = jnp.float32(float("inf"))
                self._part_t1 = jnp.float32(float("-inf"))
            if bank_cfg is not None:
                self._last_srv = jnp.zeros((n, n), jnp.float32)
                self._bw_bytes = jnp.asarray(top.bandwidth / 8.0, jnp.float32)
        # inference-serving layer (repro.net.serve): the static key maps
        # None AND rate<=0 to None, under which nothing below runs and the
        # engines compile the literal serve-free programs (the degenerate
        # limit tests/test_serve.py pins bitwise)
        self.serve_cfg = serve_cfg
        self._serve = None
        if serve_cfg is not None:
            from repro.net import serve as serve_lib
            self._serve = serve_lib.serve_key(serve_cfg)
        if self._serve is not None:
            serve_lib.validate_serve(self._serve, cfg.engine, mesh)
            self._equeue, self._eislot, ib = serve_lib.extend_queue(
                self._equeue, self._eislot, n, self._serve, cfg.seed
            )
            self._infer_base = jnp.int32(ib)
            self._sstate = serve_lib.init_serve_state(n, self._serve)
            self._serve_base = serve_lib.serve_base_key(
                cfg.seed, self._serve
            )
        if obs_cfg is not None and obs_cfg.hist is not None:
            # streaming histograms ride inside MetricsState.hist; the
            # propagation latch starts from the ACTUAL initial state and
            # the arrival FIFO is sized by the serve queue (0 without it)
            from repro.obs import hist as hist_lib
            qcap = int(self._serve.queue_cap) if self._serve is not None else 0
            hstate = hist_lib.init_hist(
                obs_cfg.hist, self.replicas.dags, queue_cap=qcap
            )
            if mesh is not None:
                hstate = mesh_lib.replicate(hstate, mesh)
            self._metrics = self._metrics._replace(hist=hstate)

    # --- replica access ----------------------------------------------------

    @property
    def bank(self):
        return self.replicas.bank

    def read(self, i) -> DagState:
        """Node i's replica: one compiled gather, counted in ``read_calls``
        (not ``device_calls``: a read advances no state)."""
        self.read_calls += 1
        with jax.profiler.TraceAnnotation("repro.net.read"):
            return replica_lib.read_replica(self.replicas, i)

    def write(self, i, dag: DagState, bank=None) -> None:
        self.replicas = replica_lib.write_replica(self.replicas, i, dag)
        if bank is not None:
            self.replicas = self.replicas._replace(bank=bank)

    # --- bank transport (only when constructed with bank_cfg) ---------------

    @property
    def bank_state(self) -> Optional[BankState]:
        return self.replicas.bank_state

    def read_view(self, i) -> DagState:
        """Node i's USABLE view: with the bank gossiped, rows whose model
        chunks have not arrived are masked out (``bank.gate_view``) so
        Algorithm 2 cannot select or approve a payload-less transaction;
        without bank gossip this is exactly ``read`` (the PR-3 view)."""
        self.read_calls += 1
        with jax.profiler.TraceAnnotation("repro.net.read"):
            dag = replica_lib.read_replica(self.replicas, i)
            if self.bank_cfg is None:
                return dag
            gate = mesh_lib.replicated_jit(bank_lib.gate_view, self.mesh)
            return gate(dag, self.replicas.bank_state.have[i], self._digest)

    def bank_commit(self, node_id, slot, params) -> None:
        """Account a stage-4 commit in the transport state: the committer
        holds the new chunks, every other node's presence bits for the
        (ring-reused) slot reset, and the digest row is re-derived."""
        if self.bank_cfg is None:
            return
        bstate = self.replicas.bank_state
        have, self._digest = self._dispatch(
            "bank_commit", _bank_commit_jit,
            bstate.have, self._digest, params,
            jnp.asarray(slot, jnp.int32), jnp.asarray(node_id, jnp.int32),
        )
        self.replicas = self.replicas._replace(
            bank_state=bstate._replace(have=have)
        )

    def missing_chunks(self, label: Optional[str] = None) -> np.ndarray:
        """(N,) referenced-but-unavailable chunks per node — the payload lag
        behind row visibility (all zeros without bank gossip). ``label``
        routes the read through the ``_fetch`` funnel."""
        if self.bank_cfg is None:
            return np.zeros(self.topology.num_nodes, np.int32)
        count = mesh_lib.replicated_jit(bank_lib.missing_chunks, self.mesh,
                                        impl=self.bank_cfg.impl)
        out = count(self.replicas.dags, self.replicas.bank_state, self._digest)
        return np.asarray(out) if label is None else self._fetch(label, out)

    def bytes_sent(self) -> float:
        """Total payload bytes delivered so far (the Table-I traffic bill)."""
        if self.bank_cfg is None:
            return 0.0
        return float(jnp.sum(self.replicas.bank_state.sent))

    def union(self) -> DagState:
        merge = mesh_lib.replicated_jit(replica_lib.merge_all, self.mesh)
        return merge(self.replicas.dags)

    def synced(self) -> bool:
        """Fully converged: row-identical replicas AND — when the bank is
        gossiped — every referenced model payload delivered (the same
        predicate the bank-aware ``converge`` loop evaluates on device)."""
        rows = bool(replica_lib.replicas_synced_jit(self.replicas.dags))
        if self.bank_cfg is None:
            return rows
        return rows and int(self.missing_chunks().max()) == 0

    def missing_rows(self, union: Optional[DagState] = None,
                     label: Optional[str] = None) -> np.ndarray:
        """(N,) rows each replica lacks vs the union view (0 = converged).
        Pass a precomputed ``union()`` to avoid re-folding the replicas;
        ``label`` routes the read through the ``_fetch`` funnel."""
        if union is None:
            union = self.union()
        out = replica_lib.missing_vs_union_jit(self.replicas.dags, union)
        return np.asarray(out) if label is None else self._fetch(label, out)

    # --- telemetry (only when constructed with obs_cfg) ---------------------

    def trace_host(self, t, kind, src, dst, arg=0.0) -> None:
        """Buffer a host-side trace span (PUBLISH/COMMIT/PARTITION — events
        the FL driver already knows host-side, so recording them costs zero
        device dispatches). Merged with the device ring at drain. No-op
        without telemetry."""
        if self.obs_cfg is not None and self.obs_cfg.trace:
            self._host_events.append(
                (float(t), int(kind), int(src), int(dst), float(arg))
            )

    def trace_device(self, t, kind, src, dst, arg=0.0) -> None:
        """Record a host-initiated span through the DEVICE trace ring —
        the ``ObsConfig.device_spans`` path: the same (t, kind, src, dst,
        arg) record ``trace_host`` buffers, appended via
        ``TraceRing.append_edges`` instead (one jitted dispatch; values
        quantize to the ring's f32 wire precision). Pinned against the
        host-recorded path in ``tests/test_hist.py``. No-op without
        telemetry/trace."""
        if self.obs_cfg is None or not self.obs_cfg.trace:
            return
        n = self.topology.num_nodes
        self._ring = self._dispatch(
            "trace_device", _trace_one_jit(n), self._ring,
            jnp.float32(t), jnp.int32(kind), jnp.int32(src),
            jnp.int32(dst), jnp.float32(arg),
        )

    def trace_span(self, t, kind, src, dst, arg=0.0) -> None:
        """PUBLISH/COMMIT entry point for the FL driver: routes to the
        device ring when ``ObsConfig.device_spans`` is set, to the host
        buffer otherwise (the default, free path)."""
        if self.obs_cfg is not None and self.obs_cfg.device_spans:
            self.trace_device(t, kind, src, dst, arg)
        else:
            self.trace_host(t, kind, src, dst, arg)

    def _note_partition(self, t: float) -> None:
        """Record the partition's begin/heal transitions once each, the
        first time the clock reaches them."""
        if self.obs_cfg is None or self.partition is None:
            return
        from repro.obs import trace as obs_trace
        p = self.partition
        if not self._part_logged[0] and t >= p.t_start:
            self._part_logged[0] = True
            self.trace_host(p.t_start, obs_trace.KIND_PARTITION, -1, -1, 1.0)
        if not self._part_logged[1] and t >= p.t_end:
            self._part_logged[1] = True
            self.trace_host(p.t_end, obs_trace.KIND_PARTITION, -1, -1, 0.0)

    def obs_report(self):
        """Drain the in-loop collectors into a host-side ``ObsReport``
        (``repro.obs.export``) — metric series truncated to the samples
        taken, the trace ring merged with buffered host spans, dispatch
        counts, and final-state scalars. ``None`` without telemetry."""
        if self.obs_cfg is None:
            return None
        from repro import obs as obs_lib
        from repro.obs import trace as obs_trace
        m = self._metrics
        taken = int(min(int(m.cursor), m.t.shape[0]))
        series = {
            "t": np.asarray(m.t, np.float64)[:taken],
            "tips": np.asarray(m.tips, np.int64)[:taken],
            "staleness": np.asarray(m.staleness, np.int64)[:taken],
            "rows_delta": np.asarray(m.rows_delta, np.int64)[:taken],
            "chunk_lag": np.asarray(m.chunk_lag, np.int64)[:taken],
            "bytes_total": np.asarray(m.bytes_total, np.float64)[:taken],
            "staleness_node": np.asarray(m.staleness_node, np.int64)[:taken],
            "staleness_link": np.asarray(m.staleness_link, np.int64)[:taken],
            "rejected": np.asarray(m.rejected, np.int64)[:taken],
            "quarantined": np.asarray(m.quarantined, np.int64)[:taken],
            "requests_served": np.asarray(
                m.requests_served, np.int64)[:taken],
            "serve_staleness": np.asarray(
                m.serve_staleness, np.int64)[:taken],
        }
        final = {
            "bytes_sent": self.bytes_sent(),
            "chunk_lag": float(self.missing_chunks().max()),
            "staleness": float(self.missing_rows().max()),
        }
        if self.faults_cfg is not None and self._fstate is not None:
            final["rejected"] = float(np.asarray(self._fstate.rejects).sum())
            final["quarantined"] = float(self.quarantined_links().sum())
        hist = None
        if self.obs_cfg.hist is not None:
            from repro.obs import hist as hist_lib
            hist = hist_lib.report_dict(m.hist, self.obs_cfg.hist)
        return obs_lib.ObsReport(
            num_nodes=self.topology.num_nodes,
            engine=self.cfg.engine,
            rounds=int(m.rounds),
            series=series,
            rows_merged=np.asarray(m.rows_merged, np.int64),
            link_bytes=np.asarray(m.link_bytes, np.float64),
            samples_dropped=int(m.dropped),
            trace=obs_trace.drain(self._ring, self._host_events),
            trace_dropped=int(self._ring.dropped),
            dispatch_counts=dict(self.dispatch_counts),
            final=final,
            hist=hist,
        )

    # --- fault injection (only when constructed with faults_cfg) ------------

    def quarantined_links(self) -> np.ndarray:
        """(N, N) bool — links the digest-verification defense has cut
        (``rejects >= quarantine_after``). All-False without faults or
        without bank gossip (bankless faults carry no rejection state)."""
        n = self.topology.num_nodes
        if self.faults_cfg is None or self._fstate is None:
            return np.zeros((n, n), bool)
        return np.asarray(
            self._fstate.rejects >= self.faults_cfg.quarantine_after
        )

    def rejection_credit(self, label: Optional[str] = None) -> Optional[np.ndarray]:
        """(N,) per-sender trust from cumulative digest rejections
        (``repro.core.anomaly.rejection_credit``) — 1.0 for clean senders,
        floored near 0 for quarantined spoofers. ``None`` without a
        fault-state carry. ``label`` routes the read through the ``_fetch``
        funnel."""
        if self.faults_cfg is None or self._fstate is None:
            return None
        from repro.core import anomaly
        out = anomaly.rejection_credit(self._fstate.rejects)
        return np.asarray(out) if label is None else self._fetch(label, out)

    def tainted_in_views(self) -> np.ndarray:
        """(N,) corrupted chunks REFERENCED by rows visible in each node's
        gated view — the attack-success numerator: with digest
        verification on this must be identically zero (corrupted payloads
        are rejected before they can set presence bits, so ``gate_view``
        never exposes a row backed by them)."""
        n = self.topology.num_nodes
        out = np.zeros(n, np.int64)
        if (self.faults_cfg is None or self._fstate is None
                or self.bank_cfg is None):
            return out
        tainted = np.asarray(self._fstate.tainted)
        for i in range(n):
            view = self.read_view(i)
            slots = np.asarray(view.model_slot)[np.asarray(view.publisher) >= 0]
            slots = np.unique(slots[slots >= 0])
            out[i] = int(tainted[i, slots, :].sum())
        return out

    def fault_report(self) -> Optional[dict]:
        """Host-side summary of the adversary/defense state: roles, the
        per-link rejection matrix, quarantined-link count, per-node
        tainted-chunk counts, and the attack-success numerator
        (``tainted_in_views``). ``None`` without fault injection."""
        if self.faults_cfg is None:
            return None
        report = {
            "roles": np.asarray(self.faults_cfg.roles, np.int32),
            "verify_digests": self.faults_cfg.verify_digests,
        }
        if self._fstate is not None:
            rejects = np.asarray(self._fstate.rejects)
            report.update(
                rejects=rejects,
                rejected_total=int(rejects.sum()),
                quarantined_links=int(self.quarantined_links().sum()),
                tainted_chunks=np.asarray(
                    self._fstate.tainted.sum(axis=(1, 2))
                ),
                tainted_in_views=self.tainted_in_views(),
                rejection_credit=self.rejection_credit(),
            )
        return report

    # --- the clock ---------------------------------------------------------

    def _mask_at(self, t: float):
        if self.partition is not None and self.partition.active(t):
            return self._part_mask
        return self._all_mask

    def _dispatch(self, label: str, fn, *args):
        """Issue ONE jitted device call through the counting funnel.

        EVERY state-advancing dispatch (tick advance, event advance, bank
        variants, converge, commit accounting) routes through here, so
        ``device_calls`` — what the ``dispatch_batching`` bench reports —
        counts them all instead of the hand-instrumented subset it used to
        see; ``dispatch_counts`` keeps the per-entry-point breakdown. The
        call runs inside a ``jax.profiler.TraceAnnotation`` named
        ``repro.net.<label>``, so device profiles name the overlay's phases.
        """
        self.device_calls += 1
        self.dispatch_counts[label] = self.dispatch_counts.get(label, 0) + 1
        with jax.profiler.TraceAnnotation(f"repro.net.{label}"):
            return fn(*args)

    def _fetch(self, label: str, x):
        """Read device values back to the host through the counting funnel.

        ONE blocking device->host read of the pytree ``x`` (numpy leaves
        out): every such read the DAG-FL driver loop makes routes through
        here, so ``host_syncs`` counts the host's waits on the device and
        ``sync_counts`` keeps the per-label breakdown. The wait runs inside
        a ``jax.profiler.TraceAnnotation`` named ``repro.net.wait.<label>``.
        """
        self.host_syncs += 1
        self.sync_counts[label] = self.sync_counts.get(label, 0) + 1
        with jax.profiler.TraceAnnotation(f"repro.net.wait.{label}"):
            return jax.device_get(x)

    def _run_ticks(self, ticks, part_active) -> None:
        """Execute a batch of sync ticks as ONE jitted device call."""
        fl = self.faults_cfg
        if self.bank_cfg is not None:
            fn = _advance_bank_jit(
                self.cfg.impl, self.bank_cfg.impl, self.mesh, self.obs_cfg,
                fl, self._codec,
            )
            args = (
                self.replicas.dags, self.replicas.bank_state, self._digest,
                self._key,
                jnp.asarray(ticks, jnp.int32), jnp.asarray(part_active, bool),
                self._adj, self._drop, self._stride, self._part_mask,
                self._nbr_idx, self._nbr_valid,
                self._cap_bytes, self._chunk_bytes,
            )
            if fl is not None:
                # the faulted body takes (dags, bstate, FSTATE, digest, ...,
                # period) and returns the FaultState too
                args = (args[:2] + (self._fstate,) + args[2:]
                        + (self._period,))
                if self.obs_cfg is None:
                    dags, bstate, self._fstate, self._key = self._dispatch(
                        "advance_bank", fn, *args
                    )
                else:
                    (dags, bstate, self._fstate, self._key, self._metrics,
                     self._ring) = self._dispatch(
                        "advance_bank", fn, *args, self._metrics, self._ring,
                    )
            elif self.obs_cfg is None:
                dags, bstate, self._key = self._dispatch(
                    "advance_bank", fn, *args
                )
            else:
                dags, bstate, self._key, self._metrics, self._ring = (
                    self._dispatch(
                        "advance_bank", fn, *args,
                        self._metrics, self._ring, self._obs_period,
                    )
                )
            self.replicas = self.replicas._replace(dags=dags, bank_state=bstate)
        else:
            fn = _advance_jit(self.cfg.impl, self.mesh, self.obs_cfg, fl)
            args = (
                self.replicas.dags, self._key,
                jnp.asarray(ticks, jnp.int32), jnp.asarray(part_active, bool),
                self._adj, self._drop, self._stride, self._part_mask,
                self._nbr_idx, self._nbr_valid,
            )
            if fl is not None:
                args = args + (self._period,)
                if self.obs_cfg is None:
                    dags, self._key = self._dispatch("advance", fn, *args)
                else:
                    dags, self._key, self._metrics, self._ring = (
                        self._dispatch(
                            "advance", fn, *args, self._metrics, self._ring,
                        )
                    )
            elif self.obs_cfg is None:
                dags, self._key = self._dispatch("advance", fn, *args)
            else:
                dags, self._key, self._metrics, self._ring = self._dispatch(
                    "advance", fn, *args,
                    self._metrics, self._ring, self._obs_period,
                )
            self.replicas = self.replicas._replace(dags=dags)
        self.tick += len(ticks)
        self.rounds_run += len(ticks)

    def _tick_once(self, t: float) -> None:
        """One sync tick at simulation time ``t`` (a batch of one — the
        reference granularity the batched ``advance`` is tested against)."""
        pact = self.partition is not None and self.partition.active(t)
        self._run_ticks([self.tick], [pact])

    def _advance_events(self, t: float) -> None:
        """Run every continuous-time event at or before ``t`` as ONE jitted
        while-loop dispatch (``repro.net.events``). Delivery slots recycle
        in place, so the queue state simply persists across calls."""
        if self._serve is not None:
            self._advance_events_serve(t)
            return
        from repro.net import events as events_lib

        limit = jnp.int32(self.cfg.max_events_per_advance)
        fire_cap = jnp.int32(self.cfg.max_ticks_per_advance)
        fl = self.faults_cfg
        if self.bank_cfg is not None:
            fn = events_lib._advance_events_bank_jit(
                self.cfg.impl, self.bank_cfg.impl, self.obs_cfg, fl,
                self._codec,
            )
            args = (
                self.replicas.dags, self.replicas.bank_state.have,
                self.replicas.bank_state.credit,
                self.replicas.bank_state.sent, self._last_srv,
                self._digest, self._equeue.time, self._equeue.valid,
                self._equeue.kind, self._equeue.src, self._equeue.dst,
                self._equeue.seq, self._eislot, self._key,
                jnp.float32(t), limit, fire_cap, self._part_mask,
                self._part_t0, self._part_t1, self._drop, self._nbr_idx,
                self._nbr_valid, self._bw_bytes, self._chunk_bytes,
            )
            if fl is not None:
                # the faulted body takes the FaultState after sent and
                # returns it too
                args = args[:4] + (self._fstate,) + args[4:]
                if self.obs_cfg is None:
                    (dags, bstate, self._fstate, self._last_srv, self._key,
                     qt, qv, done) = self._dispatch(
                        "advance_events_bank", fn, *args
                    )
                else:
                    (dags, bstate, self._fstate, self._last_srv, self._key,
                     qt, qv, done, self._metrics, self._ring) = (
                        self._dispatch(
                            "advance_events_bank", fn, *args,
                            self._metrics, self._ring,
                        )
                    )
            elif self.obs_cfg is None:
                dags, bstate, self._last_srv, self._key, qt, qv, done = (
                    self._dispatch("advance_events_bank", fn, *args)
                )
            else:
                (dags, bstate, self._last_srv, self._key, qt, qv, done,
                 self._metrics, self._ring) = self._dispatch(
                    "advance_events_bank", fn, *args,
                    self._metrics, self._ring,
                )
            self.replicas = self.replicas._replace(dags=dags, bank_state=bstate)
        else:
            fn = events_lib._advance_events_jit(self.cfg.impl, self.obs_cfg,
                                                fl)
            args = (
                self.replicas.dags, self._equeue.time, self._equeue.valid,
                self._equeue.kind, self._equeue.src, self._equeue.dst,
                self._equeue.seq, self._eislot, self._key, jnp.float32(t),
                limit, fire_cap, self._part_mask, self._part_t0,
                self._part_t1, self._drop, self._nbr_idx, self._nbr_valid,
            )
            if self.obs_cfg is None:
                dags, qt, qv, self._key, done = self._dispatch(
                    "advance_events", fn, *args
                )
            else:
                dags, qt, qv, self._key, done, self._metrics, self._ring = (
                    self._dispatch(
                        "advance_events", fn, *args,
                        self._metrics, self._ring,
                    )
                )
            self.replicas = self.replicas._replace(dags=dags)
        self._equeue = self._equeue._replace(time=qt, valid=qv)
        done = int(self._fetch("advance", done))
        self.tick += done
        self.rounds_run += done
        self.events_processed += done

    def _advance_events_serve(self, t: float) -> None:
        """The event advance with the inference-serving slots live
        (``repro.net.serve``): same loop, same transport program, plus
        KIND_INFER batches that never split the main key. The dict result
        avoids a combinatorial tuple-unpack over bank x faults x obs."""
        from repro.net import events as events_lib

        limit = jnp.int32(self.cfg.max_events_per_advance)
        fire_cap = jnp.int32(self.cfg.max_ticks_per_advance)
        fl = self.faults_cfg
        obs_carry = (
            (self._metrics, self._ring) if self.obs_cfg is not None else ()
        )
        if self.bank_cfg is not None:
            fn = events_lib._advance_events_bank_jit(
                self.cfg.impl, self.bank_cfg.impl, self.obs_cfg, fl,
                self._codec, self._serve,
            )
            args = (
                self.replicas.dags, self.replicas.bank_state.have,
                self.replicas.bank_state.credit,
                self.replicas.bank_state.sent, self._last_srv,
                self._digest, self._equeue.time, self._equeue.valid,
                self._equeue.kind, self._equeue.src, self._equeue.dst,
                self._equeue.seq, self._eislot, self._key,
                jnp.float32(t), limit, fire_cap, self._part_mask,
                self._part_t0, self._part_t1, self._drop, self._nbr_idx,
                self._nbr_valid, self._bw_bytes, self._chunk_bytes,
                self._sstate, self._serve_base, self._infer_base,
            )
            if fl is not None:
                args = args[:4] + (self._fstate,) + args[4:]
            out = self._dispatch(
                "advance_events_bank_serve", fn, *args, *obs_carry
            )
            self.replicas = self.replicas._replace(
                dags=out["dags"], bank_state=out["bstate"]
            )
            if fl is not None:
                self._fstate = out["fstate"]
            self._last_srv = out["last_srv"]
        else:
            fn = events_lib._advance_events_jit(
                self.cfg.impl, self.obs_cfg, fl, self._serve
            )
            args = (
                self.replicas.dags, self._equeue.time, self._equeue.valid,
                self._equeue.kind, self._equeue.src, self._equeue.dst,
                self._equeue.seq, self._eislot, self._key, jnp.float32(t),
                limit, fire_cap, self._part_mask, self._part_t0,
                self._part_t1, self._drop, self._nbr_idx, self._nbr_valid,
                self._sstate, self._serve_base, self._infer_base,
            )
            out = self._dispatch(
                "advance_events_serve", fn, *args, *obs_carry
            )
            self.replicas = self.replicas._replace(dags=out["dags"])
        self._key = out["key"]
        self._sstate = out["sstate"]
        if self.obs_cfg is not None:
            self._metrics, self._ring = out["metrics"], out["ring"]
        self._equeue = self._equeue._replace(time=out["qt"], valid=out["qv"])
        done = int(self._fetch("advance", out["done"]))
        self.tick += done
        self.rounds_run += done
        self.events_processed += done

    def serve_report(self):
        """Host-side serving summary (``repro.net.serve.report``):
        per-node served/arrivals/dropped counters, throughput inputs, and
        staleness-at-admit percentiles. None when serving is off."""
        if self._serve is None:
            return None
        from repro.net import serve as serve_lib
        return serve_lib.report(self._sstate, self._serve)

    def advance(self, t: float) -> None:
        """Run every sync tick scheduled at or before simulation time ``t``
        as one batched dispatch."""
        self._note_partition(t)
        if self.cfg.sync_period <= 0:
            self.converge(at_time=t)
            return
        if self.cfg.engine == "events":
            self._advance_events(t)
            return
        ticks, pacts = [], []
        nt = self._next_tick_t
        while nt <= t and len(ticks) < self.cfg.max_ticks_per_advance:
            ticks.append(self.tick + len(ticks))
            pacts.append(self.partition is not None and self.partition.active(nt))
            nt += self.cfg.sync_period
        if ticks:
            self._run_ticks(ticks, pacts)
        self._next_tick_t = nt
        if self._next_tick_t <= t:     # window overflowed the cap: fast-forward
            periods_behind = int((t - self._next_tick_t) // self.cfg.sync_period) + 1
            self.tick += periods_behind
            self._next_tick_t += periods_behind * self.cfg.sync_period

    def converge(self, at_time: float = float("inf")) -> bool:
        """Tick until the replicas reach fixpoint (ideal-wire flush / heal).

        ONE jitted ``lax.while_loop`` with an on-device predicate, bounded
        by ``num_nodes * max_stride`` ticks: the hop diameter is at most
        num_nodes - 1, and a stride-s link needs up to s ticks before it
        fires (stride capped at 64 here so pathological latency ratios
        cannot make the flush unbounded). A full stride cycle of unchanged
        state is a fixpoint (partition active or overlay disconnected — no
        further tick can make progress). Returns whether full sync was
        reached — it cannot be while a partition is active or the overlay
        is disconnected.
        """
        self._note_partition(at_time)
        limit = self.topology.num_nodes * min(self._max_stride, 64)
        stall_limit = min(self._max_stride, 64)
        fl = self.faults_cfg
        if self.bank_cfg is not None:
            # rows cross in <= num_nodes strided hops; chunks then drain at
            # the per-link budget — extend the bound, keep the stall check
            limit = (self.topology.num_nodes + self._drain_ticks) * min(
                self._max_stride, 64
            )
            fn = _converge_bank_jit(
                self.cfg.impl, self.bank_cfg.impl, self.mesh, self.obs_cfg,
                fl, self._codec,
            )
            args = (
                self.replicas.dags, self.replicas.bank_state, self._digest,
                self._key, jnp.asarray(self.tick, jnp.int32),
                self._mask_at(at_time), self._adj, self._drop, self._stride,
                limit, stall_limit, self._nbr_idx, self._nbr_valid,
                self._cap_bytes, self._chunk_bytes,
            )
            if fl is not None:
                args = (args[:2] + (self._fstate,) + args[2:]
                        + (self._period,))
                if self.obs_cfg is None:
                    (dags, bstate, self._fstate, self._key, tick, done,
                     synced) = self._dispatch("converge_bank", fn, *args)
                else:
                    (dags, bstate, self._fstate, self._key, tick, done,
                     synced, self._metrics, self._ring) = self._dispatch(
                        "converge_bank", fn, *args,
                        self._metrics, self._ring,
                    )
            elif self.obs_cfg is None:
                dags, bstate, self._key, tick, done, synced = self._dispatch(
                    "converge_bank", fn, *args
                )
            else:
                (dags, bstate, self._key, tick, done, synced,
                 self._metrics, self._ring) = self._dispatch(
                    "converge_bank", fn, *args,
                    self._metrics, self._ring, self._obs_period,
                )
            self.replicas = self.replicas._replace(dags=dags, bank_state=bstate)
        else:
            fn = _converge_jit(self.cfg.impl, self.mesh, self.obs_cfg, fl)
            args = (
                self.replicas.dags, self._key,
                jnp.asarray(self.tick, jnp.int32),
                self._mask_at(at_time), self._adj, self._drop, self._stride,
                limit, stall_limit, self._nbr_idx, self._nbr_valid,
            )
            if fl is not None:
                args = args + (self._period,)
                if self.obs_cfg is None:
                    dags, self._key, tick, done, synced = self._dispatch(
                        "converge", fn, *args
                    )
                else:
                    (dags, self._key, tick, done, synced,
                     self._metrics, self._ring) = self._dispatch(
                        "converge", fn, *args, self._metrics, self._ring,
                    )
            elif self.obs_cfg is None:
                dags, self._key, tick, done, synced = self._dispatch(
                    "converge", fn, *args
                )
            else:
                (dags, self._key, tick, done, synced,
                 self._metrics, self._ring) = self._dispatch(
                    "converge", fn, *args,
                    self._metrics, self._ring, self._obs_period,
                )
            self.replicas = self.replicas._replace(dags=dags)
        tick, done, synced = self._fetch("converge", (tick, done, synced))
        self.tick = int(tick)
        self.rounds_run += int(done)
        return bool(synced)
