"""Device-resident metric accumulators for the gossip overlay.

The paper's claims are time-series claims — iteration delay (Table II),
tip-count stability around Eq. (4), accuracy under abnormal nodes
(Fig. 6-11) — but the overlay's hot loops are single jitted dispatches
(``lax.scan`` advance windows, ``lax.while_loop`` flushes and event
batches), so nothing host-side can see *inside* an advance. This module
moves the collectors into the loop: ``MetricsState`` is one small pytree
that rides the scan/while carry, accumulating per-round counters and
sampling a fixed-capacity series row after every merge round / event
batch. Everything here is a PURE READ of the simulation state — no PRNG
use, no writes to dags/bank/queue — which is what makes the obs-on
trajectory bitwise the obs-off one (property-tested in
``tests/test_obs.py``).

Accumulators (exact, never dropped):

  ``rounds``       merge rounds / event batches executed;
  ``rows_merged``  (N,) rows of each node's replica changed by a round —
                   the per-node anti-entropy work actually done;
  ``link_bytes``   (N, N) cumulative payload bytes per directed link
                   (mirrors ``BankState.sent``; zero without bank gossip).

Series (fixed capacity S, one row per round/batch; overflow increments
``dropped`` and keeps the FIRST S samples — no silent wraparound):

  ``t``            sample instant: ``(tick + 1) * sync_period`` on the
                   tick engine (the tick's wall-clock position), the batch
                   instant on the event engine. A ``converge()`` flush has
                   no timeline; its samples reuse the tick arithmetic
                   (all-zero ``t`` on an ideal wire).
  ``tips``         tip count of the union view (Eq. 4's observable);
  ``staleness``    worst per-replica row lag behind the union;
  ``rows_delta``   total rows merged this round (progress per round);
  ``chunk_lag``    worst referenced-but-unavailable chunk count
                   (``bank.missing_chunks``; 0 without bank gossip);
  ``bytes_total``  cumulative payload bytes at the sample instant;
  ``staleness_node`` (S, N) the PER-NODE staleness vector behind the
                   ``staleness`` max — who is lagging, not just how far
                   (an eclipsed or crashed node shows up here long before
                   the max does on a busy overlay);
  ``staleness_link`` (S, N, N) the PER-LINK lag matrix
                   (``replica.missing_vs_peer``): entry (i, j) is the
                   occupied rows receiver i still lacks of sender j's
                   view — which SIDE of the overlay owes which rows
                   (``staleness_node`` is its row-wise view vs the union;
                   a starved receiver is a pinned row here long before
                   it dominates the max);
  ``rejected``     cumulative digest-verification rejections
                   (``repro.net.faults``; 0 without fault injection);
  ``quarantined``  directed links currently quarantined by the rejection
                   counter (0 without fault injection).

Capacity discipline matches the repo's fixed-shape rule (``EventQueue``,
``InSystemTrace``): shapes are static, overflow is counted, and the host
decides how big is big enough (``ObsConfig.series_capacity``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax.numpy as jnp

from repro.core import dag as dag_lib
from repro.core.dag import DagState
from repro.net import bank as bank_lib
from repro.net import replica as replica_lib
from repro.obs.hist import HistConfig


@dataclass(frozen=True)
class ObsConfig:
    """Telemetry knobs (frozen + hashable: it keys the jit-factory caches).

    ``series_capacity`` — metric samples kept (one per round/batch);
    ``trace_capacity`` — event records kept (``repro.obs.trace``);
    ``trace`` — record the PUBLISH/COMMIT/DELIVER/DRAIN/PARTITION event
    trace (metrics alone are cheaper when spans are not needed);
    ``tau_max`` — the staleness threshold the sampled tip count
    uses (``dag.num_tips``; default = ``DagFLConfig.tau_max``);
    ``hist`` — when set, stream every in-loop latency sample into the
    fixed-bin histograms of ``repro.obs.hist`` (``MetricsState.hist``
    carries them; None keeps that field an empty pytree and the programs
    literally hist-free); ``device_spans`` — record host-initiated
    PUBLISH/COMMIT spans through the device trace ring
    (``GossipNetwork.trace_device``) instead of the host-event list.
    """

    series_capacity: int = 2048
    trace_capacity: int = 16384
    trace: bool = True
    tau_max: float = 20.0
    hist: Optional[HistConfig] = None
    device_spans: bool = False


class MetricsState(NamedTuple):
    """The in-loop accumulator pytree (shapes static per (N, S))."""

    rounds: jnp.ndarray       # ()   i32 rounds / event batches executed
    rows_merged: jnp.ndarray  # (N,) i32 cumulative rows changed per node
    link_bytes: jnp.ndarray   # (N,N) f32 cumulative payload bytes per link
    cursor: jnp.ndarray       # ()   i32 samples attempted (monotone)
    dropped: jnp.ndarray      # ()   i32 samples past capacity (dropped)
    t: jnp.ndarray            # (S,) f32 sample instants
    tips: jnp.ndarray         # (S,) i32 union tip count
    staleness: jnp.ndarray    # (S,) i32 max rows any replica lags the union
    rows_delta: jnp.ndarray   # (S,) i32 total rows merged this round
    chunk_lag: jnp.ndarray    # (S,) i32 max referenced-but-missing chunks
    bytes_total: jnp.ndarray  # (S,) f32 cumulative payload bytes
    staleness_node: jnp.ndarray  # (S, N) i32 per-node row lag behind union
    staleness_link: jnp.ndarray  # (S, N, N) i32 rows receiver i lacks of j
    rejected: jnp.ndarray     # (S,) i32 cumulative digest rejections
    quarantined: jnp.ndarray  # (S,) i32 quarantined directed links
    requests_served: jnp.ndarray  # (S, N) i32 cumulative inference requests
    serve_staleness: jnp.ndarray  # (S,) i32 gated staleness at batch admit
                                  # (-1 = no batch admitted this sample)
    hist: Any = ()                # HistState when ObsConfig.hist is set;
                                  # () = zero leaves, the hist-free carry


def init_metrics(num_nodes: int, cfg: ObsConfig) -> MetricsState:
    s = int(cfg.series_capacity)
    return MetricsState(
        rounds=jnp.zeros((), jnp.int32),
        rows_merged=jnp.zeros((num_nodes,), jnp.int32),
        link_bytes=jnp.zeros((num_nodes, num_nodes), jnp.float32),
        cursor=jnp.zeros((), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
        t=jnp.zeros((s,), jnp.float32),
        tips=jnp.zeros((s,), jnp.int32),
        staleness=jnp.zeros((s,), jnp.int32),
        rows_delta=jnp.zeros((s,), jnp.int32),
        chunk_lag=jnp.zeros((s,), jnp.int32),
        bytes_total=jnp.zeros((s,), jnp.float32),
        staleness_node=jnp.zeros((s, num_nodes), jnp.int32),
        staleness_link=jnp.zeros((s, num_nodes, num_nodes), jnp.int32),
        rejected=jnp.zeros((s,), jnp.int32),
        quarantined=jnp.zeros((s,), jnp.int32),
        requests_served=jnp.zeros((s, num_nodes), jnp.int32),
        serve_staleness=jnp.full((s,), -1, jnp.int32),
    )


def rows_changed(new: DagState, old: DagState) -> jnp.ndarray:
    """(N,) i32 — rows of each stacked replica a merge round changed.

    A merged row moves its identity (publisher / publish_time) or its
    approval credit; payload columns ride along with the same winner, so
    these three fields witness every visible change the round rule can
    make.
    """
    ch = (
        (new.publisher != old.publisher)
        | (new.publish_time != old.publish_time)
        | (new.approval_count != old.approval_count)
    )
    return jnp.sum(ch.astype(jnp.int32), axis=-1)


def update(
    m: MetricsState,
    cfg: ObsConfig,
    t: jnp.ndarray,                   # () f32 sample instant
    dags: DagState,                   # post-round stacked replicas
    rows_delta: jnp.ndarray,          # (N,) i32 from rows_changed
    bstate: Optional[bank_lib.BankState] = None,
    digest: Optional[jnp.ndarray] = None,
    bank_impl: Optional[str] = None,
    rejects: Optional[jnp.ndarray] = None,   # (N, N) i32 cumulative rejections
    quarantine_after: int = 0,
    serve_counts: Optional[jnp.ndarray] = None,  # (N,) i32 cumulative served
    serve_stale: Optional[jnp.ndarray] = None,   # () i32 staleness at admit
) -> MetricsState:
    """Accumulate one round and sample one series row (jit-safe, pure read).

    Runs inside the advance scan / converge while-loop / event-batch loop;
    under a mesh the union fold and lag reductions are global, so GSPMD
    inserts the collectives (the sampled values are the same as the
    single-device ones, like every other cross-replica reduction here).
    ``rejects`` is the fault layer's cumulative rejection matrix (fault
    runs only); without it the rejected/quarantined samples stay zero.
    ``serve_counts`` / ``serve_stale`` are the inference-serving layer's
    cumulative per-node served counters and the max gated staleness any
    batch admitted at this instant saw (serve runs only; without them the
    requests_served row stays zero and serve_staleness the -1 sentinel).
    """
    union = replica_lib.merge_all(dags)
    tips = dag_lib.num_tips(union, t, cfg.tau_max)
    stale_node = replica_lib.missing_vs_union(dags, union)
    stale_link = replica_lib.missing_vs_peer(dags)
    stale = jnp.max(stale_node)
    if rejects is not None:
        rejected = jnp.sum(rejects)
        quar = jnp.sum((rejects >= quarantine_after).astype(jnp.int32))
    else:
        rejected = jnp.zeros((), jnp.int32)
        quar = jnp.zeros((), jnp.int32)
    if bstate is not None:
        lag = jnp.max(
            bank_lib.missing_chunks(dags, bstate, digest, impl=bank_impl)
        )
        total = jnp.sum(bstate.sent)
        link_bytes = bstate.sent
    else:
        lag = jnp.zeros((), jnp.int32)
        total = jnp.zeros((), jnp.float32)
        link_bytes = m.link_bytes
    n = dags.publisher.shape[0]
    if serve_counts is None:
        serve_counts = jnp.zeros((n,), jnp.int32)
    if serve_stale is None:
        serve_stale = jnp.full((), -1, jnp.int32)
    cap = m.t.shape[0]
    # first-S-samples policy: past capacity the scatter index goes out of
    # bounds and mode="drop" discards it — count, never wrap
    slot = jnp.where(m.cursor < cap, m.cursor, cap)
    return MetricsState(
        rounds=m.rounds + 1,
        rows_merged=m.rows_merged + rows_delta,
        link_bytes=link_bytes,
        cursor=m.cursor + 1,
        dropped=m.dropped + (m.cursor >= cap).astype(jnp.int32),
        t=m.t.at[slot].set(t, mode="drop"),
        tips=m.tips.at[slot].set(tips.astype(jnp.int32), mode="drop"),
        staleness=m.staleness.at[slot].set(
            stale.astype(jnp.int32), mode="drop"
        ),
        rows_delta=m.rows_delta.at[slot].set(
            jnp.sum(rows_delta), mode="drop"
        ),
        chunk_lag=m.chunk_lag.at[slot].set(lag.astype(jnp.int32), mode="drop"),
        bytes_total=m.bytes_total.at[slot].set(total, mode="drop"),
        staleness_node=m.staleness_node.at[slot].set(
            stale_node.astype(jnp.int32), mode="drop"
        ),
        staleness_link=m.staleness_link.at[slot].set(
            stale_link.astype(jnp.int32), mode="drop"
        ),
        rejected=m.rejected.at[slot].set(
            rejected.astype(jnp.int32), mode="drop"
        ),
        quarantined=m.quarantined.at[slot].set(quar, mode="drop"),
        requests_served=m.requests_served.at[slot].set(
            serve_counts.astype(jnp.int32), mode="drop"
        ),
        serve_staleness=m.serve_staleness.at[slot].set(
            serve_stale.astype(jnp.int32), mode="drop"
        ),
        hist=m.hist,
    )
