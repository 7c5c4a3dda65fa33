"""Continuous-time event engine: the overlay without tick quantization.

The §IV deployment model is asynchronous by construction — nodes finish
Eq. (5)-(7) iterations on their own clocks (Poisson arrivals, per-node
``h_i``), and messages cross each wireless link after that link's own
latency. Up to PR 4 the simulator approximated all of that on a quantized
global tick: a link with latency ℓ fired every ``ceil(ℓ / sync_period)``
ticks, so a 0.3 s link waited for the 1 s tick and a 3.7 s link was rounded
to 4 s. This module replaces the quantization with a device-resident
discrete-event simulation:

  queue    a fixed-capacity event queue stored as stacked arrays
           ``(time, kind, src, dst, seq)`` with a validity mask
           (``EventQueue``) — no heap, no data-dependent shapes, one
           pytree in the jitted loop's carry;
  pop      the queue head is a masked lexicographic argmin over
           ``(time, kind, seq)`` — ``repro.kernels.event_pop`` (Pallas
           kernel + ``ref.event_pop_ref`` oracle, the ``gossip_merge``
           reduction mold with min in place of max);
  advance  ONE jitted ``lax.while_loop`` pops the head, gathers every
           event firing at the same instant, processes the batch, and
           reschedules — the whole horizon is a single dispatch. Each
           delivery edge fires at most ``max_ticks_per_advance`` times per
           window; an overflowing backlog is elided (the edge jumps past
           the horizon) exactly as the tick driver fast-forwards, keeping
           the degenerate limit bitwise for any window size.

Event kinds (lexicographic tie order = intra-instant processing order,
mirroring the tick driver: rows merge, then payloads settle, then
completions land, then new iterations read):

  ``KIND_DELIVER``  anti-entropy delivery on a directed edge. Each edge
                    delivers every ``delivery_intervals`` seconds — the
                    link's ``Topology.latency`` (zero-latency links fall
                    back to the protocol's ``sync_period`` cadence) — and
                    reschedules itself; simultaneous deliveries merge as
                    ONE fused round (``gossip._apply_round``), which is
                    what makes the degenerate limit exact (below).
  ``KIND_DRAIN``    bank chunk-drain completion (``repro.net.bank``): a
                    link whose byte budget ran out mid-slot finishes its
                    next whole chunk at ``t + remaining / rate`` instead
                    of waiting for the next tick — bandwidth accrues
                    continuously (``(t - last_serviced) * B/8``), so a
                    strided-out link no longer wastes its idle ticks.
  ``KIND_PUBLISH``  iteration completion: the node publishes a transaction
                    approving the tips it reserved at start (the §IV
                    in-system simulation, ``simulate_insystem_tips``).
  ``KIND_START``    iteration start: a Poisson arrival picks a node, the
                    node samples k tips from its LOCAL replica view and
                    begins ``h_i`` seconds of Eq. (5)-(7) work.

Degenerate-limit equivalence (CI-enforced, ``tests/test_net_events.py`` +
``benchmarks/gossip_propagation.py --smoke``): with a uniform deterministic
per-edge delay equal to the sync period, deliveries fire in lockstep
batches at exactly the tick times, the engine splits its PRNG key once per
batch exactly as the tick scan splits once per tick, and the merge
sequence — dags, bank state, and key alike — is BITWISE the
``engine="ticks"`` fused path. Precision domain: the event clock lives on
device in float32 (``EventQueue.time`` accumulates ``qt + interval`` per
fire) while the tick driver's clock accumulates in host float64, so the
bitwise claim requires the common delay to accumulate exactly in float32 —
dyadic values (0.25, 0.5, 1.0, 2.0, ...); a delay like 0.1 drifts one
rounding step per fire and the two engines eventually disagree on how many
rounds fit a window. Heterogeneous latencies then depart from the
tick model in the honest direction: fast links deliver early, slow links at
their true cadence, and drains recover the bandwidth the stride model
forfeited.

``GossipNetwork(engine="events")`` (``repro.net.gossip``) swaps its
``advance`` onto this engine; ``simulate_insystem_tips`` closes the loop
with §IV by measuring the Eq. (4) tip equilibrium *inside* the full gossip
system (``benchmarks/stability_tips.py`` compares it against the closed
form and the standalone numpy simulation).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dag as dag_lib
from repro.core import stability as stability_lib
from repro.core.dag import DagState
from repro.kernels import chunk_transfer as chunk_kernel
from repro.kernels.event_pop import event_pop
from repro.net import bank as bank_lib
from repro.net import gossip as gossip_lib
from repro.net import mesh as mesh_lib
from repro.net import replica as replica_lib
from repro.net.topology import Topology, partition_matrix

KIND_DELIVER = 0   # anti-entropy delivery on edge (src -> dst)
KIND_DRAIN = 1     # bank chunk-drain completion on edge (src -> dst)
KIND_PUBLISH = 2   # iteration completion: dst publishes its transaction
KIND_START = 3     # iteration start: a node reserves tips, begins h_i work
KIND_INFER = 4     # inference-serving slot on a node (arrival / completion);
                   # sorts AFTER every transport kind at an equal instant, so
                   # same-instant requests serve the post-merge view


class EventQueue(NamedTuple):
    """Fixed-capacity event queue as stacked arrays (one jittable pytree).

    Invalid slots carry ``time = +inf`` so the head reduction never has to
    special-case them; ``seq`` is a unique per-slot tie-break (insertion
    order), which makes the pop deterministic even at exact time/kind ties.
    ``time`` is float32 (it lives on device inside the jitted loop) — see
    the module docstring for what that means for the degenerate-limit
    bitwise claim.
    """

    time: jnp.ndarray    # (Q,) f32, +inf on invalid slots
    kind: jnp.ndarray    # (Q,) i32
    src: jnp.ndarray     # (Q,) i32 sender (edge events) / acting node
    dst: jnp.ndarray     # (Q,) i32 receiver (edge events) / acting node
    seq: jnp.ndarray     # (Q,) i32 unique tie-break
    valid: jnp.ndarray   # (Q,) bool


def delivery_intervals(top: Topology, sync_period: float) -> np.ndarray:
    """(N, N) f32 inter-delivery interval per directed edge.

    The continuous-time replacement for ``gossip.stride_matrix``: an edge
    delivers every ``latency`` seconds — its actual wire time, not the
    tick-grid round-up ``ceil(latency / period) * period`` — with
    zero-latency links falling back to the protocol's ``sync_period``
    cadence (an instantaneous wire still only exchanges state as often as
    the anti-entropy protocol initiates). +inf off-link.
    """
    lat = np.where(np.isfinite(top.latency), top.latency, 0.0)
    iv = np.where(lat > 0, lat, float(sync_period))
    return np.where(top.adjacency, iv, np.inf).astype(np.float32)


def make_edge_queue(top: Topology, sync_period: float,
                    drain_slots: bool = False):
    """Build the perpetual edge-event slots for an overlay.

    One ``KIND_DELIVER`` slot per directed edge, first firing one interval
    in (matching the tick engine, whose first tick runs at one period) and
    rescheduling itself forever — edge slots recycle in place, so the queue
    can never overflow. ``drain_slots=True`` adds one (initially invalid)
    ``KIND_DRAIN`` slot per directed edge for bank gossip. An edgeless
    overlay gets a single invalid slot so reductions stay well-formed.

    Returns ``(EventQueue, slot_interval (Q,) f32)`` — the per-slot
    delivery cadence (0 on non-delivery slots).
    """
    iv = delivery_intervals(top, sync_period)
    dst, src = np.nonzero(top.adjacency)        # receiver i hears sender j
    e = len(dst)
    if e == 0:
        dst = src = np.zeros(1, np.int64)
        times = np.full(1, np.inf, np.float32)
        kinds = np.zeros(1, np.int32)
        valid = np.zeros(1, bool)
        interval = np.full(1, np.inf, np.float32)
    else:
        times = iv[dst, src].astype(np.float32)
        kinds = np.zeros(e, np.int32)
        valid = np.ones(e, bool)
        interval = times.copy()
        if drain_slots:
            dst = np.concatenate([dst, dst])
            src = np.concatenate([src, src])
            times = np.concatenate([times, np.full(e, np.inf, np.float32)])
            kinds = np.concatenate([kinds, np.full(e, KIND_DRAIN, np.int32)])
            valid = np.concatenate([valid, np.zeros(e, bool)])
            interval = np.concatenate([interval, np.zeros(e, np.float32)])
    queue = EventQueue(
        time=jnp.asarray(times, jnp.float32),
        kind=jnp.asarray(kinds, jnp.int32),
        src=jnp.asarray(src, jnp.int32),
        dst=jnp.asarray(dst, jnp.int32),
        seq=jnp.arange(len(times), dtype=jnp.int32),
        valid=jnp.asarray(valid),
    )
    return queue, jnp.asarray(interval, jnp.float32)


def _edge_mask(n: int, qdst, qsrc, mask) -> jnp.ndarray:
    """(N, N) bool — scatter queue-slot mask onto directed-edge coordinates."""
    hits = jnp.zeros((n, n), jnp.int32).at[qdst, qsrc].add(
        mask.astype(jnp.int32)
    )
    return hits > 0


def _queue_head_due(qtime, qvalid, horizon):
    return jnp.min(jnp.where(qvalid, qtime, jnp.inf)) <= horizon


def _partition_mask(t, part_mask, part_t0, part_t1):
    """(N, N) bool — the partition's edge suppression at instant ``t``
    (active on ``t_start <= t < t_end``, matching ``PartitionSchedule``)."""
    pact = (t >= part_t0) & (t < part_t1)
    return jnp.where(pact, part_mask, True)


def _deliver_round(dags, qt, fires, key, t, qv, qkind, qsrc, qdst, islot,
                   horizon, fire_cap, part_mask, part_t0, part_t1, drop,
                   nbr_idx, nbr_valid, impl):
    """One fused anti-entropy round over every delivery firing at instant
    ``t`` — THE shared block all three event drivers run, so the key-split
    order (one per batch), the partition-window rule, and the reschedule
    arithmetic that the degenerate-limit bitwise equivalence depends on
    live in one place.

    Reschedule: a fired edge moves one interval out; an edge that has
    already fired ``fire_cap`` times within this advance window instead
    jumps to its first fire time strictly past ``horizon`` — bitwise the
    tick driver's ``max_ticks_per_advance`` fast-forward, which SKIPS
    (never replays) a backlog that outruns the cap, so the degenerate
    uniform-delay limit stays bitwise the tick path for any window size.

    Returns ``(dags, qt, fires, key, deliver, live, pm)`` — the edge masks
    so bank callers can service the same exchanges.
    """
    n = dags.publisher.shape[0]
    batch = qv & (qt == t) & (qkind == KIND_DELIVER)
    deliver = _edge_mask(n, qdst, qsrc, batch)
    pm = _partition_mask(t, part_mask, part_t0, part_t1)
    key, sub = jax.random.split(key)
    u = jax.random.uniform(sub, (n, n))
    live = deliver & pm & (u >= drop)
    dags = gossip_lib._apply_round(dags, live, nbr_idx, nbr_valid, impl)
    fires = fires + batch.astype(jnp.int32)
    elide = fires >= fire_cap
    skip = (jnp.floor((horizon - qt) / islot) + 1.0) * islot
    qt = jnp.where(batch, qt + jnp.where(elide, skip, islot), qt)
    return dags, qt, fires, key, deliver, live, pm


# ---------------------------------------------------------------------------
# Engine A: GossipNetwork advance — deliveries (+ bank drains) to a horizon
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _advance_events_jit(impl: str, obs=None, faults=None, serve=None):
    """Event-driven ``advance``: one ``lax.while_loop`` over delivery batches.

    Each iteration pops the queue head (``repro.kernels.event_pop``),
    gathers every delivery firing at that instant, and runs the shared
    ``_deliver_round`` block — one PRNG split per batch, exactly as the
    tick scan splits once per tick, and per-edge fire caps that elide an
    overflowing backlog exactly as the tick driver fast-forwards — so the
    degenerate uniform-delay limit is bitwise the tick path, key included,
    for any advance window.

    ``obs`` (an ``repro.obs.ObsConfig``) threads the telemetry collectors
    through the loop carry, sampled once per event batch at the batch
    instant — a pure read, so the dags/key trajectory is bitwise the
    ``obs=None`` program, whose body below is the untouched code.
    ``faults`` (a ``repro.net.faults.FaultConfig``) swaps in the
    fault-injected body — ``faults=None`` keeps the untouched program
    below. ``serve`` (pre-mapped through ``repro.net.serve.serve_key``)
    swaps in the inference-serving body with KIND_INFER slots live;
    ``serve=None`` keeps the literal serve-free program below.
    """
    if serve is not None:
        from repro.net import serve as serve_lib   # deferred: serve imports this module
        return serve_lib._advance_events_serve_jit(impl, serve, obs, faults)
    if faults is not None:
        from repro.net import faults as faults_lib   # deferred: faults imports this module
        return faults_lib._advance_events_faults_jit(impl, faults, obs)

    if obs is None:
        def advance(dags, qtime, qvalid, qkind, qsrc, qdst, qseq, islot, key,
                    horizon, limit, fire_cap, part_mask, part_t0, part_t1,
                    drop, nbr_idx, nbr_valid):

            def cond(carry):
                _dags, qt, qv, _fires, _key, done = carry
                return _queue_head_due(qt, qv, horizon) & (done < limit)

            def body(carry):
                dags, qt, qv, fires, key, done = carry
                idx, _found = event_pop(qt, qkind, qseq, qv)
                t = qt[idx]
                dags, qt, fires, key, _dlv, _live, _pm = _deliver_round(
                    dags, qt, fires, key, t, qv, qkind, qsrc, qdst, islot,
                    horizon, fire_cap, part_mask, part_t0, part_t1, drop,
                    nbr_idx, nbr_valid, impl,
                )
                return dags, qt, qv, fires, key, done + 1

            dags, qt, qv, _fires, key, done = jax.lax.while_loop(
                cond, body,
                (dags, qtime, qvalid, jnp.zeros_like(qseq), key, jnp.int32(0)),
            )
            return dags, qt, qv, key, done

        return jax.jit(advance)

    from repro import obs as obs_lib   # deferred: repro.obs imports repro.net

    def advance(dags, qtime, qvalid, qkind, qsrc, qdst, qseq, islot, key,
                horizon, limit, fire_cap, part_mask, part_t0, part_t1, drop,
                nbr_idx, nbr_valid, metrics, ring):

        def cond(carry):
            _dags, qt, qv = carry[0], carry[1], carry[2]
            done = carry[7]
            return _queue_head_due(qt, qv, horizon) & (done < limit)

        def body(carry):
            dags, qt, qv, fires, key, metrics, ring, done = carry
            idx, _found = event_pop(qt, qkind, qseq, qv)
            t = qt[idx]
            old = dags
            dags, qt, fires, key, _dlv, live, _pm = _deliver_round(
                dags, qt, fires, key, t, qv, qkind, qsrc, qdst, islot,
                horizon, fire_cap, part_mask, part_t0, part_t1, drop,
                nbr_idx, nbr_valid, impl,
            )
            metrics, ring = obs_lib.observe_round(
                obs, metrics, ring, t, old, dags, live_edges=live
            )
            return dags, qt, qv, fires, key, metrics, ring, done + 1

        dags, qt, qv, _fires, key, metrics, ring, done = jax.lax.while_loop(
            cond, body,
            (dags, qtime, qvalid, jnp.zeros_like(qseq), key, metrics, ring,
             jnp.int32(0)),
        )
        return dags, qt, qv, key, done, metrics, ring

    return jax.jit(advance)


@functools.lru_cache(maxsize=None)
def _advance_events_bank_jit(impl: str, bank_impl, obs=None, faults=None,
                             codec=None, serve=None):
    """Event-driven ``advance`` with the model bank gossiped.

    The row half of a batch is the shared ``_deliver_round`` (fire caps and
    all); the bank half services every edge whose delivery or drain fired,
    with a budget
    accrued CONTINUOUSLY since the edge's last service
    (``(t - last_serviced) * B/8`` — the tick model's per-fire quantum is
    the uniform-interval special case, so the unlimited-capacity degenerate
    limit stays bitwise the tick path). A serviced link with work left over
    arms its drain slot at the instant its next whole chunk completes; a
    link partitioned away retries one chunk-time later without resetting
    the rolled-over credit. ``obs`` threads the telemetry carry exactly as
    in ``_advance_events_jit`` (``obs=None`` keeps the untouched program);
    bank batches additionally sample chunk lag / byte totals and record a
    DRAIN trace span per link that moved payload. ``faults`` swaps in the
    fault-injected body (``faults=None`` keeps the untouched program
    below). ``codec`` (pre-mapped through ``delta_codec.codec_key``)
    scales ``chunk_bytes`` to the encoded wire size — pricing, the byte
    meter, AND the drain-instant arithmetic all see the compressed
    granule, so compressed chunks complete earlier in continuous time;
    ``codec=None`` keeps the literal raw-chunk program. ``serve``
    (pre-mapped through ``repro.net.serve.serve_key``) swaps in the
    inference-serving body with KIND_INFER slots live — requests served
    from the availability-GATED view; ``serve=None`` keeps the literal
    serve-free program below.
    """
    if serve is not None:
        from repro.net import serve as serve_lib
        return serve_lib._advance_events_bank_serve_jit(
            impl, bank_impl, serve, obs, faults, codec
        )
    if faults is not None:
        from repro.net import faults as faults_lib
        return faults_lib._advance_events_bank_faults_jit(
            impl, bank_impl, faults, obs, codec
        )

    if obs is not None:
        from repro import obs as obs_lib

    def advance(dags, have, credit, sent, last_srv, digest, qtime, qvalid,
                qkind, qsrc, qdst, qseq, islot, key, horizon, limit,
                fire_cap, part_mask, part_t0, part_t1, drop, nbr_idx,
                nbr_valid, bw_bytes, chunk_bytes, *obs_carry):
        if codec is not None:
            chunk_bytes = chunk_bytes * codec.wire_ratio()
        n = dags.publisher.shape[0]

        def cond(carry):
            qt, qv, done = carry[4], carry[5], carry[7]
            return _queue_head_due(qt, qv, horizon) & (done < limit)

        def body(carry):
            if obs is not None:
                (dags, bstate, last_srv, key, qt, qv, fires, done,
                 metrics, ring) = carry
                old_dags, old_sent, old_have = dags, bstate.sent, bstate.have
            else:
                dags, bstate, last_srv, key, qt, qv, fires, done = carry
            idx, _found = event_pop(qt, qkind, qseq, qv)
            t = qt[idx]
            batch = qv & (qt == t)
            is_drn = qkind == KIND_DRAIN
            drain = _edge_mask(n, qdst, qsrc, batch & is_drn)

            # drain-only batches (whole-chunk completions between delivery
            # instants) skip the anti-entropy round AND its PRNG split — a
            # drain moves payload bytes, not rows. Deliveries always take
            # the round branch, so the degenerate unlimited-capacity limit
            # (where drains never arm) is untouched.
            def _with_round(op):
                return _deliver_round(
                    *op, t, qv, qkind, qsrc, qdst, islot, horizon, fire_cap,
                    part_mask, part_t0, part_t1, drop, nbr_idx, nbr_valid,
                    impl,
                )

            def _no_round(op):
                dags, qt, fires, key = op
                off = jnp.zeros((n, n), bool)
                pm = _partition_mask(t, part_mask, part_t0, part_t1)
                return dags, qt, fires, key, off, off, pm

            dags, qt, fires, key, deliver, live, pm = jax.lax.cond(
                jnp.any(batch & (qkind == KIND_DELIVER)),
                _with_round, _no_round, (dags, qt, fires, key),
            )
            # bank service: surviving deliveries carry chunks in the same
            # exchange; drains are transfer continuations (partition-gated,
            # not loss-gated). Budget = continuous accrual since last fire.
            svc = live | (drain & pm)
            sched = deliver | drain
            accr = jnp.where(svc, (t - last_srv) * bw_bytes, 0.0)
            sat = chunk_kernel.chunk_dedup(bstate.have, digest, impl=bank_impl)
            bstate, pending = bank_lib.chunk_step(
                dags, bstate, digest, sat, sat, svc, accr, chunk_bytes,
                return_pending=True,
            )
            # a fired-but-suppressed exchange wastes its window (idle
            # bandwidth is never banked) — the accrual clock resets either way
            last_srv = jnp.where(sched, t, last_srv)
            # drain slots: serviced edges re-arm from `pending` at the next
            # whole-chunk completion; suppressed fired drains retry later.
            # Strict progress: f32 accrual residue can leave `credit` within
            # one ulp-of-t's worth of bytes of a whole chunk, making the
            # completion instant round back to t itself — the drain would
            # re-arm at its own time and livelock the advance against
            # max_events_per_advance, starving every event behind it. Clamp
            # each re-arm to the next representable instant (a no-op for any
            # re-arm that already lands strictly past t).
            rate = jnp.maximum(bw_bytes, 1e-9)
            t_next = jnp.nextafter(t, jnp.float32(jnp.inf))
            e_next = jnp.maximum(
                t + (chunk_bytes - bstate.credit) / rate, t_next
            )[qdst, qsrc]
            e_retry = jnp.maximum(t + chunk_bytes / rate, t_next)[qdst, qsrc]
            e_svc = svc[qdst, qsrc]
            e_pend = pending[qdst, qsrc]
            qv = jnp.where(is_drn & e_svc, e_pend, qv)
            qt = jnp.where(is_drn & e_svc,
                           jnp.where(e_pend, e_next, jnp.inf), qt)
            qt = jnp.where(batch & is_drn & ~e_svc, e_retry, qt)
            if obs is not None:
                metrics2, ring2 = obs_lib.observe_round(
                    obs, metrics, ring, t, old_dags, dags, live_edges=live,
                    bytes_delta=bstate.sent - old_sent, bstate=bstate,
                    digest=digest, bank_impl=bank_impl, old_have=old_have,
                )
                return (dags, bstate, last_srv, key, qt, qv, fires, done + 1,
                        metrics2, ring2)
            return dags, bstate, last_srv, key, qt, qv, fires, done + 1

        init = (dags, bank_lib.BankState(have=have, credit=credit, sent=sent),
                last_srv, key, qtime, qvalid, jnp.zeros_like(qseq),
                jnp.int32(0)) + tuple(obs_carry)
        out = jax.lax.while_loop(cond, body, init)
        dags, bstate, last_srv, key, qt, qv, _fires, done = out[:8]
        return (dags, bstate, last_srv, key, qt, qv, done) + out[8:]

    return jax.jit(advance)


# ---------------------------------------------------------------------------
# Engine B: the §IV in-system simulation — Eq. (4) inside the full overlay
# ---------------------------------------------------------------------------


class InSystemTrace(NamedTuple):
    """Trace of the in-system tip process (one sample per publish event).

    ``tips`` counts tips of the UNION view (the paper's omniscient external
    agent E) under the same ``tip_mask`` rule Algorithm 2 samples from;
    ``staleness`` is the worst per-replica row lag behind that union at the
    same instants — the quantity that inflates the tip count past Eq. (4)
    when gossip is slow. ``union`` is the final union ledger (per-node
    publish counters live on it); ``overflow`` counts dropped work (queue
    or trace capacity) and is asserted zero by the tests/benches.
    """

    times: np.ndarray       # (P,) f64 publish instants
    tips: np.ndarray        # (P,) f64 union tip count after each publish
    staleness: np.ndarray   # (P,) f64 max rows any replica lags the union
    published: int          # transactions published (excl. genesis)
    overflow: int
    union: DagState
    trace: Optional[dict] = None   # drained PUBLISH/COMMIT device records
                                   # (``record_trace=True`` runs only)
    trace_dropped: int = 0

    def tail_mean(self, frac: float = 0.5) -> float:
        return stability_lib.tail_mean(self.tips, frac)

    def to_report(self):
        """Fold this bespoke trace into the shared ``repro.obs`` format.

        Returns an ``ObsReport`` whose series are the per-publish
        ``t``/``tips``/``staleness`` samples and whose trace is the
        device-recorded PUBLISH/COMMIT record set (empty without
        ``record_trace``) — so ``metrics_jsonl_lines`` /
        ``chrome_trace`` / ``write_*`` work on tip-sim runs unchanged.
        ``tail_mean`` stays the stability acceptance metric; this is the
        export path only.
        """
        from repro.obs.export import ObsReport
        pub = np.asarray(self.union.publisher)
        occ = pub >= 0
        # genesis is published by the virtual node id N, so the max
        # occupied publisher id IS the node count
        n = int(pub[occ].max()) if occ.any() else 0
        trace = self.trace if self.trace is not None else {
            "t": np.zeros((0,), np.float64),
            "kind": np.zeros((0,), np.int32),
            "src": np.zeros((0,), np.int32),
            "dst": np.zeros((0,), np.int32),
            "arg": np.zeros((0,), np.float64),
        }
        return ObsReport(
            num_nodes=n,
            engine="insystem",
            rounds=int(self.published),
            series={
                "t": np.asarray(self.times, np.float64),
                "tips": np.asarray(self.tips, np.float64),
                "staleness": np.asarray(self.staleness, np.float64),
            },
            rows_merged=np.zeros((n,), np.int64),
            link_bytes=np.zeros((n, n), np.float64),
            samples_dropped=int(self.overflow),
            trace=trace,
            trace_dropped=int(self.trace_dropped),
            final={"published": float(self.published)},
        )


@functools.lru_cache(maxsize=None)
def _tip_sim_jit(impl: str, k: int, e_slots: int, p_slots: int,
                 record_trace: bool = False):
    """The in-system §IV driver: one jitted while_loop over ALL event kinds.

    Deliveries batch exactly as in engine A; a START samples a node
    (uniform, the paper's global Poisson arrival), reserves k tips from
    that node's LOCAL replica view (gumbel top-k, in-flight iterations may
    overlap — the overlap Eq. (4) absorbs), and schedules its PUBLISH
    ``h_i`` seconds out in a recycled pending slot; a PUBLISH lands the
    transaction at the globally-sequenced row of the publisher's replica,
    credits the reserved approvals, and samples the union tip count.

    ``record_trace`` threads a ``repro.obs.trace.TraceRing`` through the
    carry and emits the publisher's spans FROM INSIDE the jitted loop —
    one KIND_PUBLISH record when a START reserves its tips (arg = the
    node's ``h_i`` duration) and one KIND_COMMIT when the PUBLISH lands
    (arg = global sequence) — the device-side counterpart of the host
    ``trace_host`` spans. False (the default, its own cache entry) keeps
    the literal trace-free program.
    """
    start_slot = e_slots + p_slots
    if record_trace:
        from repro.obs import trace as obs_trace

    def _self_edge(n, node):
        ids = jnp.arange(n, dtype=jnp.int32)
        return (ids[:, None] == node) & (ids[None, :] == node)

    def run(dags, qtime, qvalid, qkind, qsrc, qdst, qseq, islot, pend, h,
            rate, tau_max, horizon, limit, drop, nbr_idx, nbr_valid,
            part_mask, part_t0, part_t1, key, trace_t, trace_tips,
            trace_stale, *obs_carry):
        n = dags.publisher.shape[0]
        tcap = trace_t.shape[0]
        key, k0 = jax.random.split(key)
        qtime = qtime.at[start_slot].set(jax.random.exponential(k0) / rate)

        def cond(carry):
            qt, qv, done = carry[1], carry[2], carry[-1]
            return _queue_head_due(qt, qv, horizon) & (done < limit)

        def body(carry):
            (dags, qt, qv, qd, pend, key, seqc, tt, ttips, tst, cur, ovf,
             *rest) = carry
            done = rest[-1]
            rest = tuple(rest[:-1])
            idx, _found = event_pop(qt, qkind, qseq, qv)
            t = qt[idx]
            knd = qkind[idx]

            def do_deliver(op):
                (dags, qt, qv, qd, pend, key, seqc, tt, ttips, tst, cur,
                 ovf, *rest) = op
                # fire_cap = imax: the tip sim never elides (it has no tick
                # twin to stay bitwise with; the horizon is one advance)
                dags, qt, _f, key, _dlv, _live, _pm = _deliver_round(
                    dags, qt, jnp.zeros_like(qseq), key, t, qv, qkind, qsrc,
                    qd, islot, horizon, jnp.int32(jnp.iinfo(jnp.int32).max),
                    part_mask, part_t0, part_t1, drop, nbr_idx, nbr_valid,
                    impl,
                )
                return (dags, qt, qv, qd, pend, key, seqc, tt, ttips, tst,
                        cur, ovf, *rest)

            def do_publish(op):
                (dags, qt, qv, qd, pend, key, seqc, tt, ttips, tst, cur,
                 ovf, *rest) = op
                node = qd[idx]
                dag_i = jax.tree_util.tree_map(lambda x: x[node], dags)
                row, new_count = replica_lib.global_row(dag_i, seqc)
                dag_i = dag_lib.publish_at(
                    dag_i, row, new_count, node, t, pend[idx],
                    jnp.float32(0.5), jnp.float32(0.0), row,
                )
                dags = jax.tree_util.tree_map(
                    lambda x, v: x.at[node].set(v), dags, dag_i
                )
                qv = qv.at[idx].set(False)
                qt = qt.at[idx].set(jnp.inf)
                union = replica_lib.merge_all(dags)
                tips = dag_lib.num_tips(union, t, tau_max)
                stale = jnp.max(replica_lib.missing_vs_union(dags, union))
                slot = jnp.minimum(cur, tcap - 1)
                tt = tt.at[slot].set(t)
                ttips = ttips.at[slot].set(tips.astype(jnp.float32))
                tst = tst.at[slot].set(stale.astype(jnp.float32))
                ovf = ovf + (cur >= tcap).astype(jnp.int32)
                cur = jnp.minimum(cur + 1, tcap)
                if record_trace:
                    (ring,) = rest
                    ring = obs_trace.append_edges(
                        ring, t, obs_trace.KIND_COMMIT, _self_edge(n, node),
                        seqc.astype(jnp.float32),
                    )
                    rest = (ring,)
                return (dags, qt, qv, qd, pend, key, seqc + 1, tt, ttips,
                        tst, cur, ovf, *rest)

            def do_start(op):
                (dags, qt, qv, qd, pend, key, seqc, tt, ttips, tst, cur,
                 ovf, *rest) = op
                key, kn, ks, ka = jax.random.split(key, 4)
                node = jax.random.randint(kn, (), 0, n)
                dag_i = jax.tree_util.tree_map(lambda x: x[node], dags)
                rows, _nv = dag_lib.select_tips(dag_i, ks, k, t, tau_max)
                pv = jax.lax.dynamic_slice_in_dim(qv, e_slots, p_slots)
                free = jnp.argmin(pv)                 # first invalid slot
                has = ~pv[free]
                slot = (e_slots + free).astype(jnp.int32)
                qv = qv.at[slot].set(qv[slot] | has)
                qt = qt.at[slot].set(jnp.where(has, t + h[node], qt[slot]))
                qd = qd.at[slot].set(jnp.where(has, node, qd[slot]))
                pend = pend.at[slot].set(jnp.where(has, rows, pend[slot]))
                qt = qt.at[start_slot].set(
                    t + jax.random.exponential(ka) / rate
                )
                ovf = ovf + (~has).astype(jnp.int32)
                if record_trace:
                    # an iteration dropped for want of a pending slot never
                    # publishes — no span for it either
                    (ring,) = rest
                    ring = obs_trace.append_edges(
                        ring, t, obs_trace.KIND_PUBLISH,
                        _self_edge(n, node) & has, h[node],
                    )
                    rest = (ring,)
                return (dags, qt, qv, qd, pend, key, seqc, tt, ttips, tst,
                        cur, ovf, *rest)

            branch = jnp.where(
                knd == KIND_DELIVER, 0,
                jnp.where(knd == KIND_PUBLISH, 1, 2),
            )
            op = (dags, qt, qv, qd, pend, key, seqc, tt, ttips, tst, cur,
                  ovf) + rest
            out = jax.lax.switch(branch, [do_deliver, do_publish, do_start], op)
            return tuple(out) + (done + 1,)

        init = (dags, qtime, qvalid, qdst, pend, key, jnp.int32(1),
                trace_t, trace_tips, trace_stale, jnp.int32(0),
                jnp.int32(0)) + tuple(obs_carry) + (jnp.int32(0),)
        out = jax.lax.while_loop(cond, body, init)
        (dags, _qt, _qv, _qd, _pend, _key, seqc, tt, ttips, tst, cur,
         ovf) = out[:12]
        done = out[-1]
        return (dags, tt, ttips, tst, cur, ovf, seqc, done) + out[12:-1]

    return jax.jit(run)


def simulate_insystem_tips(
    top: Topology,
    h,                              # per-node Eq. (7) delay: (N,) or scalar
    arrival_rate: float,            # lambda — global Poisson iteration rate
    k: int,                         # approvals per transaction
    tau_max: float,
    horizon: float,
    capacity: int = 256,
    seed: int = 0,
    sync_period: float = 1.0,       # cadence fallback for zero-latency links
    impl: str = "fused",
    partition=None,                 # Optional[gossip.PartitionSchedule]
    max_pending: int = 64,
    trace_cap: Optional[int] = None,
    record_trace: bool = False,
) -> InSystemTrace:
    """Measure the Eq. (4) tip process INSIDE the full gossip system.

    The standalone ``core.stability.simulate_tip_count`` runs the paper's
    M/G/inf tangle on one global tip set; this runs the same arrival/
    completion process against per-node DAG replicas synced by the
    continuous-time engine — nodes reserve tips from their own (possibly
    stale) views and publish into their own replicas, so gossip staleness,
    duplicate approvals, and partitions become visible in the measured
    equilibrium. With a well-connected overlay and delivery intervals well
    under ``h`` the tail mean reproduces ``stability.equilibrium_tips``
    (the bench-grid acceptance, ``benchmarks/stability_tips.py``); slow
    overlays inflate it (``examples/async_stragglers.py``).

    ``record_trace=True`` additionally threads a device-resident
    ``repro.obs.trace.TraceRing`` through the jitted loop and drains it
    into ``InSystemTrace.trace`` (one PUBLISH span per started
    iteration, one COMMIT per landed transaction) — the shared
    ``repro.obs`` record format ``InSystemTrace.to_report()`` exports.
    The measured series is bitwise-unchanged either way (pinned by
    ``tests/test_hist.py``).
    """
    if sync_period <= 0:
        raise ValueError("in-system tip sim needs a positive sync_period")
    n = top.num_nodes
    h = jnp.asarray(np.broadcast_to(np.asarray(h, np.float32), (n,)))
    dag = dag_lib.empty_dag(capacity, k, n + 1)
    dag = dag_lib.publish(
        dag, jnp.asarray(n, jnp.int32), jnp.float32(0.0),
        jnp.full((k,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(0, jnp.int32),
    )
    dags = jax.tree_util.tree_map(lambda x: jnp.repeat(x[None], n, axis=0), dag)

    base, islot_e = make_edge_queue(top, sync_period)
    e = int(base.time.shape[0])
    p = int(max_pending)
    qtime = jnp.concatenate([base.time, jnp.full((p + 1,), jnp.inf, jnp.float32)])
    qkind = jnp.concatenate([
        base.kind,
        jnp.full((p,), KIND_PUBLISH, jnp.int32),
        jnp.full((1,), KIND_START, jnp.int32),
    ])
    qsrc = jnp.concatenate([base.src, jnp.zeros((p + 1,), jnp.int32)])
    qdst = jnp.concatenate([base.dst, jnp.zeros((p + 1,), jnp.int32)])
    qseq = jnp.arange(e + p + 1, dtype=jnp.int32)
    qvalid = jnp.concatenate(
        [base.valid, jnp.zeros((p,), bool), jnp.ones((1,), bool)]
    )
    islot = jnp.concatenate([islot_e, jnp.zeros((p + 1,), jnp.float32)])
    pend = jnp.full((e + p + 1, k), dag_lib.NO_TX, jnp.int32)

    if trace_cap is None:
        trace_cap = int(horizon * arrival_rate * 3) + 64
    trace_t = jnp.zeros((trace_cap,), jnp.float32)
    trace_tips = jnp.zeros((trace_cap,), jnp.float32)
    trace_stale = jnp.zeros((trace_cap,), jnp.float32)

    iv = delivery_intervals(top, sync_period)
    deliveries = float((horizon / iv[top.adjacency]).sum()) if top.adjacency.any() else 0.0
    limit = int(min(deliveries + 4.0 * horizon * arrival_rate + p + 1024,
                    2.0 ** 31 - 1))
    if partition is not None:
        part_mask = jnp.asarray(partition_matrix(partition.assignment))
        pt0, pt1 = float(partition.t_start), float(partition.t_end)
    else:
        part_mask = jnp.ones((n, n), bool)
        pt0, pt1 = float("inf"), float("-inf")

    nbr_idx, nbr_valid = gossip_lib._neighbor_table_cached(
        np.asarray(top.adjacency, bool).tobytes(), n
    )
    obs_carry = ()
    if record_trace:
        from repro.obs import trace as obs_trace
        ring0 = obs_trace.init_trace(2 * trace_cap + 8)
        obs_carry = (ring0,)
    out = _tip_sim_jit(impl, k, e, p, record_trace=record_trace)(
        dags, qtime, qvalid, qkind, qsrc, qdst, qseq, islot, pend, h,
        jnp.float32(arrival_rate), jnp.float32(tau_max), jnp.float32(horizon),
        jnp.int32(limit), jnp.asarray(top.drop), nbr_idx, nbr_valid,
        part_mask, jnp.float32(pt0), jnp.float32(pt1),
        jax.random.PRNGKey(seed), trace_t, trace_tips, trace_stale,
        *obs_carry,
    )
    dags, tt, ttips, tst, cur, ovf, seqc, _done = out[:8]
    span_trace, span_dropped = None, 0
    if record_trace:
        from repro.obs import trace as obs_trace
        ring = out[8]
        span_trace = obs_trace.drain(ring)
        span_dropped = int(ring.dropped)
    cur = int(cur)
    return InSystemTrace(
        times=np.asarray(tt, np.float64)[:cur],
        tips=np.asarray(ttips, np.float64)[:cur],
        staleness=np.asarray(tst, np.float64)[:cur],
        published=int(seqc) - 1,
        overflow=int(ovf),
        union=mesh_lib.replicated_jit(replica_lib.merge_all, None)(dags),
        trace=span_trace,
        trace_dropped=span_dropped,
    )
