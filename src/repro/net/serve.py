"""Poisson inference load served from availability-gated bank views.

The paper's deployment story (§III, Algorithm 2) is that on-device nodes
keep *using* their local model while consensus proceeds asynchronously —
training never blocks serving, and serving never waits for global sync.
Up to PR 8 the simulator only trained: ``launch/serve.py`` batches
requests against a static checkpoint, disconnected from the gossip /
bank / event machinery. This module closes that loop on the continuous-
time event engine (``repro.net.events``):

  arrivals   each node receives inference requests as an independent
             Poisson process at ``ServeConfig.rate`` requests/s. Inter-
             arrival gaps are sampled from a dedicated key branch
             (``fold_in(PRNGKey(seed), salt)`` folded per (node, arrival
             count) — the salted-fold_in discipline ``repro.net.faults``
             uses), so the training PRNG stream sees the EXACT same split
             sequence as a serve-free run.
  service    a fixed-slot batching model per node, the ``SlotServer``
             shape from ``launch/serve.py`` flattened to counters: an
             idle node admits up to ``slots`` queued requests as one
             batch and completes them ``service_time`` seconds later
             (one lockstep decode pass); requests arriving past
             ``queue_cap`` waiting are counted dropped, never silently
             lost.
  staleness  at every batch-admit instant the node's AVAILABILITY-GATED
             view is measured against the union ledger: a request sees
             only rows whose model chunks have physically arrived
             (``bank.rows_available`` over the live presence bitmaps),
             so staleness-at-serve-time is the transport's doing — slow
             Table-I links, partitions, and quarantined links all show
             up in the served-model lag, not in a simulated penalty.

Event mechanics: ``extend_queue`` appends 2N perpetual ``KIND_INFER``
slots to the edge queue — N arrival slots (self-rescheduling, like
delivery edges) and N batch-completion slots (armed at admit, disarmed
at completion). INFER sorts after every transport kind at an equal
instant, so a same-instant delivery batch pops first and the request is
served from the *post-merge* view. INFER batches never split the main
PRNG key — the serve layer draws only from its own fold_in branch. The
INFER head is a step of the event engine's one loop body
(``events._build_advance``), which runs ``infer_step`` below in place of
the transport step.

Degenerate limit (the obs=None / faults=None / codec=None pattern):
``serve_key`` maps ``None`` and any ``rate <= 0`` config to ``None``,
under which the serve step is never traced and the engine compiles its
LITERAL pre-serve program — the PR-8 trajectory, replicas / bank state /
PRNG key alike, is preserved bitwise by construction (pinned in
``tests/test_serve.py`` and the ``--smoke`` tripwire).

Entry points: ``GossipNetwork(serve_cfg=ServeConfig(...))`` →
``serve_report()``; ``run_dagfl_gossip(serve=...)`` →
``extras["serve_report"]``; ``benchmarks/serve_load.py`` sweeps Table-I
link classes; ``docs/SERVING.md`` documents the semantics.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.net import bank as bank_lib
from repro.net import events as events_lib
from repro.net import replica as replica_lib

# fold_in salt for the serve key branch: arrival gaps derive from
# fold_in(fold_in(fold_in(PRNGKey(seed), _SALT_SERVE), node), count) —
# never from the training stream (events.py splits are untouched)
_SALT_SERVE = 13


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static, hashable inference-load knobs (a jit-factory cache key).

    ``rate``             Poisson request arrivals per node per second.
                         ``rate <= 0`` degenerates to no serving at all —
                         ``serve_key`` maps it to ``None`` so the engines
                         compile the literal serve-free program.
    ``slots``            batch slots per node (the ``SlotServer`` shape):
                         an idle node admits up to this many queued
                         requests as one lockstep batch.
    ``service_time``     seconds one batch takes (prefill + decode for
                         the whole lockstep batch).
    ``queue_cap``        waiting requests a node buffers; arrivals past
                         it are counted in ``ServeState.dropped``.
    ``sample_capacity``  staleness-at-admit samples kept (first-K, the
                         repo's no-wraparound capacity discipline).
    ``salt``             fold_in salt for the serve key branch.
    """

    rate: float = 1.0
    slots: int = 4
    service_time: float = 0.05
    queue_cap: int = 64
    sample_capacity: int = 4096
    salt: int = _SALT_SERVE


def serve_key(cfg: Optional[ServeConfig]) -> Optional[ServeConfig]:
    """The static jit-factory key: ``None`` for every config that serves
    nothing, so a ``rate=0.0`` network compiles the IDENTICAL pre-serve
    program (the ``delta_codec.codec_key`` pattern — off is not a branch
    inside the jitted body, off is a different, literal program)."""
    if cfg is None or cfg.rate <= 0:
        return None
    return cfg


def validate_serve(cfg: ServeConfig, engine: str, mesh=None) -> None:
    """Reject configs the event machinery cannot honor (effective — i.e.
    post-``serve_key`` — configs only; ``None``/rate-0 is valid anywhere
    because it changes nothing)."""
    if engine != "events":
        raise ValueError(
            "serve_cfg needs the continuous-time engine — construct with "
            "GossipConfig(engine='events') (Poisson arrivals have no tick "
            "grid to quantize onto)"
        )
    if mesh is not None:
        raise NotImplementedError(
            "inference serving is single-device for now — the serve "
            "counters are not mesh-sharded (see ROADMAP open items)"
        )
    if cfg.slots < 1:
        raise ValueError("ServeConfig.slots must be >= 1")
    if cfg.queue_cap < 1:
        raise ValueError("ServeConfig.queue_cap must be >= 1")
    if cfg.service_time <= 0:
        raise ValueError("ServeConfig.service_time must be > 0")


class ServeState(NamedTuple):
    """Per-node serving counters + the staleness-at-admit sample buffer
    (one small pytree riding the event loop's carry, like ``MetricsState``).

    Counters are (N,) int32; the sample buffer keeps the FIRST K admit
    instants (capacity ``ServeConfig.sample_capacity``) with overflow
    counted in ``sdropped`` — the ``repro.obs`` discipline, never a wrap.
    """

    queued: jnp.ndarray     # (N,) i32 requests waiting
    inflight: jnp.ndarray   # (N,) i32 requests in the current batch
    served: jnp.ndarray     # (N,) i32 requests completed
    arrivals: jnp.ndarray   # (N,) i32 requests arrived (also the PRNG counter)
    dropped: jnp.ndarray    # (N,) i32 arrivals past queue_cap
    batches: jnp.ndarray    # (N,) i32 batches admitted
    st: jnp.ndarray         # (K,) f32 admit instants
    snode: jnp.ndarray      # (K,) i32 admitting node
    sstale: jnp.ndarray     # (K,) i32 gated staleness at admit
    cursor: jnp.ndarray     # ()   i32 samples attempted (monotone)
    sdropped: jnp.ndarray   # ()   i32 samples past capacity


def init_serve_state(num_nodes: int, cfg: ServeConfig) -> ServeState:
    n, k = int(num_nodes), int(cfg.sample_capacity)
    z = jnp.zeros((n,), jnp.int32)
    return ServeState(
        queued=z, inflight=z, served=z, arrivals=z, dropped=z, batches=z,
        st=jnp.zeros((k,), jnp.float32),
        snode=jnp.full((k,), -1, jnp.int32),
        sstale=jnp.full((k,), -1, jnp.int32),
        cursor=jnp.zeros((), jnp.int32),
        sdropped=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Arrival PRNG: a dedicated fold_in branch, reproducible per (seed, node)
# ---------------------------------------------------------------------------


def serve_base_key(seed: int, cfg: ServeConfig):
    """The serve layer's key branch root. Derived from the same seed the
    network uses but salted off it — the training stream never sees a
    serve-dependent split."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), cfg.salt)


def arrival_key(base, node, count):
    """Key for one node's ``count``-th inter-arrival gap. Pure function of
    (seed, node, count): arrivals replay exactly, on device or host, with
    no sequential RNG state anywhere."""
    return jax.random.fold_in(jax.random.fold_in(base, node), count)


def interarrival_gap(base, node, count, rate):
    """() f32 — the exponential gap BEFORE arrival ``count`` at ``node``."""
    return jax.random.exponential(arrival_key(base, node, count)) / rate


def _next_gaps(base, counts, rate):
    """(N,) f32 — each node's next gap given its per-node arrival counts."""
    n = counts.shape[0]
    keys = jax.vmap(arrival_key, in_axes=(None, 0, 0))(
        base, jnp.arange(n, dtype=jnp.int32), counts.astype(jnp.int32)
    )
    return jax.vmap(jax.random.exponential)(keys) / jnp.float32(rate)


def arrival_times(seed: int, cfg: ServeConfig, node: int,
                  horizon: float) -> np.ndarray:
    """Host-side replay of one node's arrival instants up to ``horizon``
    (the f32 accumulation the engine performs). Test/analysis helper —
    the property tests pin the engine's counters against it."""
    base = serve_base_key(seed, cfg)
    t = np.float32(0.0)
    out, count = [], 0
    while True:
        gap = np.float32(interarrival_gap(
            base, jnp.int32(node), jnp.int32(count), jnp.float32(cfg.rate)
        ))
        t = np.float32(t + gap)
        if float(t) > horizon:
            return np.asarray(out, np.float64)
        out.append(float(t))
        count += 1


# ---------------------------------------------------------------------------
# Queue extension: 2N perpetual KIND_INFER slots
# ---------------------------------------------------------------------------


def extend_queue(queue: events_lib.EventQueue, islot, num_nodes: int,
                 cfg: ServeConfig, seed: int):
    """Append the serve slots to an edge queue built by ``make_edge_queue``.

    Slot ``infer_base + i`` is node i's ARRIVAL slot (valid, first firing
    at the count-0 exponential gap, self-rescheduling forever like a
    delivery edge); slot ``infer_base + N + i`` is node i's batch
    COMPLETION slot (invalid until a batch admits, like a drain slot).
    Returns ``(EventQueue, islot, infer_base)``. Only called when serve is
    effective — a serve-free network's queue is untouched, which is what
    keeps the degenerate limit the literal PR-8 program.
    """
    n = int(num_nodes)
    base = serve_base_key(seed, cfg)
    first = _next_gaps(base, jnp.zeros((n,), jnp.int32), cfg.rate)
    infer_base = int(queue.time.shape[0])
    ids = jnp.arange(n, dtype=jnp.int32)
    ext = events_lib.EventQueue(
        time=jnp.concatenate([
            queue.time, first.astype(jnp.float32),
            jnp.full((n,), jnp.inf, jnp.float32),
        ]),
        kind=jnp.concatenate([
            queue.kind, jnp.full((2 * n,), events_lib.KIND_INFER, jnp.int32),
        ]),
        src=jnp.concatenate([queue.src, ids, ids]),
        dst=jnp.concatenate([queue.dst, ids, ids]),
        seq=jnp.arange(infer_base + 2 * n, dtype=jnp.int32),
        valid=jnp.concatenate([
            queue.valid, jnp.ones((n,), bool), jnp.zeros((n,), bool),
        ]),
    )
    islot = jnp.concatenate([islot, jnp.zeros((2 * n,), jnp.float32)])
    return ext, islot, infer_base


# ---------------------------------------------------------------------------
# The INFER batch step (runs inside the jitted event loop)
# ---------------------------------------------------------------------------


def gated_staleness(dags, sat=None) -> jnp.ndarray:
    """(N,) i32 — rows each node's USABLE view lacks vs the union ledger.

    Without a bank (``sat=None``) this is plain replica staleness
    (``missing_vs_union``). With the availability bitmaps it first masks
    rows whose chunks have not arrived (``bank.gate_views``) — the
    staleness a served request actually experiences: a row whose metadata
    gossiped ahead of its payload is NOT usable yet, so it still counts
    as missing.
    """
    union = replica_lib.merge_all(dags)
    if sat is None:
        return replica_lib.missing_vs_union(dags, union)
    return replica_lib.missing_vs_union(
        bank_lib.gate_views(dags, sat), union
    )


def infer_step(cfg: ServeConfig, sstate: ServeState, t, qt, qv, qkind, qseq,
               infer_base, serve_base, stale_now):
    """Process every KIND_INFER event firing at instant ``t``.

    Order inside the instant (all fused, one pass): completions land
    (inflight → served, server idles), arrivals enqueue (or drop past
    ``queue_cap``), then every idle node with waiting work admits a batch
    of up to ``slots`` — so a completion and an arrival at the same
    instant chain into an immediate re-admit, the self-healing property
    that keeps a loaded server busy. Admission samples the node's gated
    staleness ``stale_now`` into the first-K buffer.

    Reschedules: a fired arrival slot moves to the node's next
    exponential gap (keyed by the post-increment arrival count);
    completion slots of touched nodes arm at ``t + service_time`` when a
    batch admitted, disarm otherwise. Draws only from ``serve_base`` —
    the main key is neither passed in nor split.

    Returns ``(sstate, qt, qv, admitted (N,) bool, batch_now (N,) i32)``.
    """
    n = stale_now.shape[0]
    is_inf = qkind == events_lib.KIND_INFER
    fired = qv & (qt == t) & is_inf
    arr_slot = is_inf & (qseq < infer_base + n)
    node_of = jnp.clip(
        jnp.where(arr_slot, qseq - infer_base, qseq - infer_base - n),
        0, n - 1,
    )
    zeros_b = jnp.zeros((n,), bool)
    arr_fire = zeros_b.at[node_of].max(fired & arr_slot)
    cmp_fire = zeros_b.at[node_of].max(fired & ~arr_slot)

    # completions first: the batch finishes, the server idles
    served = sstate.served + jnp.where(cmp_fire, sstate.inflight, 0)
    inflight = jnp.where(cmp_fire, 0, sstate.inflight)
    # arrivals: count every one (the count also indexes the PRNG branch),
    # enqueue while there is room, drop past the cap
    arrivals = sstate.arrivals + arr_fire.astype(jnp.int32)
    room = sstate.queued < cfg.queue_cap
    queued = sstate.queued + (arr_fire & room).astype(jnp.int32)
    dropped = sstate.dropped + (arr_fire & ~room).astype(jnp.int32)
    # admission: idle + backlog -> start a batch NOW (same instant)
    can = (inflight == 0) & (queued > 0)
    batch_now = jnp.where(can, jnp.minimum(queued, cfg.slots), 0)
    inflight = inflight + batch_now
    queued = queued - batch_now
    batches = sstate.batches + can.astype(jnp.int32)

    # staleness-at-admit samples: prefix-sum slot assignment, first-K,
    # mode="drop" past capacity (the repro.obs scatter discipline)
    cap = sstate.st.shape[0]
    fi = can.astype(jnp.int32)
    pos = jnp.cumsum(fi) - fi
    idx = sstate.cursor + pos
    slot = jnp.where(can & (idx < cap), idx, cap)
    tvec = jnp.broadcast_to(jnp.asarray(t, jnp.float32), (n,))
    st = sstate.st.at[slot].set(tvec, mode="drop")
    snode = sstate.snode.at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop"
    )
    sstale = sstate.sstale.at[slot].set(
        stale_now.astype(jnp.int32), mode="drop"
    )
    cursor = sstate.cursor + jnp.sum(fi)
    sdropped = sstate.sdropped + jnp.sum(fi * (idx >= cap).astype(jnp.int32))

    # reschedule fired arrival slots at the next per-(node, count) gap
    next_arr = t + _next_gaps(serve_base, arrivals, cfg.rate)
    qt = jnp.where(fired & arr_slot, next_arr[node_of], qt)
    # completion slots: arm at t + service_time when a batch admitted,
    # disarm when the node went idle; untouched nodes keep their schedule
    touched = cmp_fire | can
    e_cmp = is_inf & ~arr_slot & touched[node_of]
    qv = jnp.where(e_cmp, can[node_of], qv)
    qt = jnp.where(
        e_cmp,
        jnp.where(can[node_of], t + jnp.float32(cfg.service_time), jnp.inf),
        qt,
    )
    out = ServeState(
        queued=queued, inflight=inflight, served=served, arrivals=arrivals,
        dropped=dropped, batches=batches, st=st, snode=snode, sstale=sstale,
        cursor=cursor, sdropped=sdropped,
    )
    return out, qt, qv, can, batch_now


# ---------------------------------------------------------------------------
# Host-side report
# ---------------------------------------------------------------------------


def report(sstate: ServeState, cfg: ServeConfig) -> dict:
    """Drain the serve counters into a host-side dict (all numpy/python).

    ``staleness_p50`` / ``staleness_p99`` are percentiles over the
    staleness-at-admit samples actually kept (NaN with zero batches);
    per-node arrays carry the full served / arrived / dropped / batch
    accounting so benches can derive throughput per node.
    """
    served = np.asarray(sstate.served, np.int64)
    k = int(min(int(sstate.cursor), sstate.sstale.shape[0]))
    stale = np.asarray(sstate.sstale, np.int64)[:k]
    out = {
        "rate": float(cfg.rate),
        "slots": int(cfg.slots),
        "service_time": float(cfg.service_time),
        "requests_served": served,
        "served_total": int(served.sum()),
        "arrivals": np.asarray(sstate.arrivals, np.int64),
        "arrived_total": int(np.asarray(sstate.arrivals, np.int64).sum()),
        "queued": np.asarray(sstate.queued, np.int64),
        "inflight": np.asarray(sstate.inflight, np.int64),
        "dropped": np.asarray(sstate.dropped, np.int64),
        "dropped_total": int(np.asarray(sstate.dropped, np.int64).sum()),
        "batches": np.asarray(sstate.batches, np.int64),
        "samples": k,
        "samples_dropped": int(sstate.sdropped),
        "staleness_t": np.asarray(sstate.st, np.float64)[:k],
        "staleness_node": np.asarray(sstate.snode, np.int64)[:k],
        "staleness_samples": stale,
        "staleness_p50": (float(np.percentile(stale, 50)) if k
                          else float("nan")),
        "staleness_p99": (float(np.percentile(stale, 99)) if k
                          else float("nan")),
        "staleness_max": int(stale.max()) if k else 0,
    }
    return out
