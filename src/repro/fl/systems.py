"""The FL systems of Section V, sharing one task/population/latency model.

* DAG-FL          — the paper's system (core consensus on a shared ledger).
* DAG-FL gossip   — same consensus, but each node works against its own DAG
                    replica synced by anti-entropy gossip over an overlay
                    (repro.net); the §III.A architecture under an imperfect
                    network. With an ideal wire it recovers plain DAG-FL.
* Google FL       — synchronous rounds of 10, FederatedAveraging [1].
* Asynchronous FL — server mixes each upload into the global model [7].
* Block FL        — 5 miner groups, candidate blocks (5 tx or 10 s), PoW [3].

Timing comes from the Table-I ``LatencyModel``; iteration starts follow the
paper's Poisson arrivals ("one node on average ready per second"). Google FL
serializes its cohort's transfers over the shared 100 Mbps medium, which is
what makes its rounds the slowest (Table II).
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DagFLConfig
from repro.core import Controller, make_dagfl_iteration
from repro.core.controller import host_read
from repro.core.consensus import commit_prepared, make_dagfl_stages
from repro.core.anomaly import contribution_rates
from repro.fl.latency import LatencyModel
from repro.fl.nodes import SimNode
from repro.fl.tasks import make_epoch_train
from repro.net import gossip as gossip_lib
from repro.net import replica as replica_lib
from repro.net import topology as topo_lib
from repro.net.bank import BankGossipConfig
from repro.obs import ObsConfig
from repro.obs import trace as obs_trace


@dataclass
class SimConfig:
    iterations: int = 400
    eval_every: int = 25
    minibatch: int = 32
    steps_per_iter: int = 4       # minibatches per 'iteration' (one local epoch)
    val_size: int = 64            # node-local validation batch (fixed shape)
    seed: int = 0
    async_mix: float = 0.5        # [7]-style server mixing coefficient
    block_margin: float = 0.2     # miner drops tx if acc < global_acc - margin
                                  # (loose: catches poisoned models, not the
                                  #  normal non-IID accuracy dip)
    backdoor_joint_bias: float = 3.0


@dataclass
class SimResult:
    system: str
    iters: np.ndarray
    times: np.ndarray
    accs: np.ndarray
    avg_latency: float            # mean per-iteration latency (Table II)
    final_params: Any
    extras: Dict = field(default_factory=dict)

    def acc_at(self, iteration: int) -> float:
        if len(self.iters) == 0:
            return 0.0
        i = np.searchsorted(self.iters, iteration, side="right") - 1
        return float(self.accs[max(i, 0)])


def _poisson_starts(rng, rate: float, n: int) -> np.ndarray:
    return np.cumsum(rng.exponential(1.0 / rate, n))


def _jb(batch: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
    return {k: jnp.asarray(v) for k, v in batch.items()}


def _counter_snapshot(dag) -> Dict[str, jnp.ndarray]:
    """Raw cumulative counters (Table IV) at a point in time, on the device
    (the driver reads them back through its backend's ``fetch``)."""
    return dict(
        contribution_m0=dag.contributing_m0,
        contribution_m1=dag.contributing_m1,
        published=dag.published_per_node,
    )


def _late_contributions(dag, mid_snapshot: Dict, extras: Dict) -> None:
    """Second-half contribution rates from a mid-run counter snapshot.

    The paper's Table IV runs 10000 s; at bench scale the first half is
    pre-convergence fog where validation cannot yet separate abnormal models.
    """
    if not mid_snapshot:
        return
    pub_late = np.asarray(dag.published_per_node) - mid_snapshot["published"]
    for m in (0, 1):
        c_late = (
            np.asarray(getattr(dag, f"contributing_m{m}"))
            - mid_snapshot[f"contribution_m{m}"]
        )
        extras[f"late_contribution_m{m}"] = c_late / np.maximum(pub_late, 1)
    extras["late_published"] = pub_late


# ---------------------------------------------------------------------------
# DAG-FL: one event-driven Algorithm-2 loop, two ledger backends
# ---------------------------------------------------------------------------
#
# All jit wrappers live at module level (cached): a benchmark sweep that
# constructs a fresh backend/task per run used to re-trace prepare + commit
# every time; now equal configs and tasks (frozen dataclasses) share one
# trace.


@functools.lru_cache(maxsize=None)
def _jit_of(fn):
    """jit cache keyed by function identity — every backend instance using
    the same commit body shares one traced executable."""
    return jax.jit(fn)


def _identity_train(params, batch, key):
    """Lazy-node 'training' (§V.A): republish the aggregated model as-is."""
    return params, {}


def _build_stage_jits(dcfg, task, weighted):
    # a task's frozen device state (``task.frozen``: ``LoRALMTask``'s base,
    # none for the paper's tasks) is the jitted prepare's first argument, so
    # the program reads it in place instead of baking it in as constants
    frozen = getattr(task, "frozen", ())
    bind = getattr(task, "bind", lambda state: task)

    def jitted(make_train):
        def prepare(state, dag, bank, now, key, train_batch, val_batch, node_bias=None):
            t = bind(state)
            stage, _ = make_dagfl_stages(dcfg, t.eval_fn, make_train(t), weighted)
            return stage(dag, bank, now, key, train_batch, val_batch, node_bias)

        return functools.partial(jax.jit(prepare), frozen)

    return jitted(make_epoch_train), jitted(lambda t: _identity_train), commit_prepared


_stage_jits_cached = functools.lru_cache(maxsize=None)(_build_stage_jits)


def _stage_jits(dcfg, task, weighted):
    """(jitted prepare, jitted lazy prepare, commit body) for a run.

    ``DagFLConfig`` and the paper tasks are frozen dataclasses, so sweeps
    that rebuild an equal task per run hit the cache and stop re-tracing
    stages 1-3 (``LoRALMTask`` hashes by identity: one task, one trace); an
    unhashable ad-hoc task just falls back to a fresh trace.
    """
    try:
        return _stage_jits_cached(dcfg, task, weighted)
    except TypeError:
        return _build_stage_jits(dcfg, task, weighted)


class _SharedLedger:
    """One instantly-consistent global DAG — the paper's idealized runtime."""

    name = "dagfl"

    def __init__(self, state, commit_fn):
        self.dag, self.bank = state.dag, state.bank
        self._commit = _jit_of(commit_fn)

    def view(self, node_id):
        return self.dag

    def advance(self, t):
        pass

    fetch = staticmethod(host_read)

    def commit(self, node_id, t1, prepared):
        self.dag, self.bank = self._commit(
            self.dag, self.bank, node_id, jnp.float32(t1), prepared
        )

    def union_dag(self):
        return self.dag

    def observe(self, done, t1, union):
        pass

    def extras(self, union):
        return {}


def _run_dagfl_events(task, nodes, dcfg, sim, global_val, weighted, make_backend):
    """Event-driven driver shared by ``run_dagfl`` and ``run_dagfl_gossip``:
    prepare (stages 1-3) at start time t0, commit (stage 4) at completion
    t1 = t0 + h — in-flight iterations overlap, so tips accumulate to the
    Eq.-4 equilibrium instead of being consumed serially. The backend
    decides what ledger state a node sees (global vs its own replica);
    keeping one copy of the loop is what guarantees the gossip system's
    ideal-wire limit stays exactly equivalent to the shared ledger.

    Wall-clock spans (``jax.profiler.TraceAnnotation``, on the profiler's
    clock when one records): ``repro.fl.start`` and ``repro.fl.commit``
    carry the iteration index that pairs them, ``repro.fl.check`` wraps
    the agent's check, and a start splits into ``repro.fl.inputs`` (the
    time, key, batches and bias it passes) and ``repro.fl.prepare`` (the
    dispatch)."""
    rng = np.random.default_rng(sim.seed)
    lat = LatencyModel.create(dcfg, sim.seed)
    gv = _jb(global_val)
    N = len(nodes)

    ctrl = Controller(dcfg, task.eval_fn)
    params0 = task.init(jax.random.PRNGKey(sim.seed))
    state = ctrl.genesis(params0, gv)

    prep_normal, prep_lazy, commit_fn = _stage_jits(dcfg, task, weighted)
    backend = make_backend(state, commit_fn)

    if sim.iterations == 0:
        # no Poisson starts -> no commits: report the genesis state instead
        # of reaching the trailing eval with an unbound completion time
        union = backend.union_dag()
        extras = {
            "contribution_m0": np.asarray(contribution_rates(union, 0)),
            "contribution_m1": np.asarray(contribution_rates(union, 1)),
            "published": np.asarray(union.published_per_node),
            "behaviors": [n.behavior for n in nodes],
            "dag": union,
        }
        extras.update(backend.extras(union))
        empty = np.zeros((0,))
        return SimResult(backend.name, empty, empty, empty, 0.0, params0, extras)

    # joint backdoor attack: backdoor nodes up-weight backdoor publishers
    is_bd = np.array([n.behavior == "backdoor" for n in nodes] + [False])
    bd_bias = jnp.asarray(np.where(is_bd, sim.backdoor_joint_bias, 0.0), jnp.float32)
    zero_bias = jnp.zeros_like(bd_bias)

    starts = _poisson_starts(rng, dcfg.arrival_rate, sim.iterations)
    pending = []        # heap of (t1, seq, node_id, Prepared)
    curve, lats = [], []
    done = 0
    mid_snapshot = {}

    def _commit_one(t1, i, nid, prepared):
        nonlocal done
        with jax.profiler.TraceAnnotation("repro.fl.commit", iteration=i):
            backend.advance(t1)
            backend.commit(nid, t1, prepared)
            done += 1
            if done == sim.iterations // 2 and not mid_snapshot:
                mid_snapshot.update(backend.fetch(
                    "snapshot", _counter_snapshot(backend.union_dag())))

    def _check(t1):
        nonlocal state
        with jax.profiler.TraceAnnotation("repro.fl.check"):
            union = backend.union_dag()
            state.dag, state.bank = union, backend.bank
            state = ctrl.check(state, jax.random.PRNGKey(done), float(t1) + 1e-3,
                               gv, fetch=backend.fetch)
            curve.append((done, t1, state.best_accuracy))
            backend.observe(done, t1, union)

    for i, t0 in enumerate(starts):
        while pending and pending[0][0] <= t0:
            t1, seq, nid, prepared = heapq.heappop(pending)
            _commit_one(t1, seq, nid, prepared)
            if done % sim.eval_every == 0:
                _check(t1)
        with jax.profiler.TraceAnnotation("repro.fl.start", iteration=i):
            backend.advance(t0)
            node = nodes[rng.integers(0, N)]
            lazy = node.behavior == "lazy"
            t1 = t0 + lat.dagfl_iteration(node.node_id, lazy=lazy)
            # telemetry hook: backends with an event trace record the
            # iteration span (PUBLISH at t0, duration t1 - t0) — a
            # host-side note, free
            on_start = getattr(backend, "on_start", None)
            if on_start is not None:
                on_start(node.node_id, t0, t1)
            fn = prep_lazy if lazy else prep_normal
            view = backend.view(node.node_id)
            # the training batch is drawn before the validation batch: the
            # node's RNG stream, and so the trajectory, depends on the order
            with jax.profiler.TraceAnnotation("repro.fl.inputs"):
                now = jnp.float32(t0)
                key = jax.random.PRNGKey(sim.seed * 100003 + i)
                batch = _jb(node.epoch(sim.steps_per_iter, sim.minibatch))
                val = _jb(node.val_batch(sim.val_size))
                bias = bd_bias if node.behavior == "backdoor" else zero_bias
                # defense hook: backends carrying fault state fold their
                # rejection credit into tip selection — log(1.0) = 0 for
                # clean senders, so without rejections this adds an exact
                # zero and the trajectory is untouched
                fb = getattr(backend, "fault_bias", lambda: None)()
                if fb is not None:
                    bias = bias + fb
            with jax.profiler.TraceAnnotation("repro.fl.prepare"):
                prepared = fn(view, backend.bank, now, key, batch, val, bias)
            heapq.heappush(pending, (t1, i, node.node_id, prepared))
            lats.append(t1 - t0)
    while pending:
        t1, seq, nid, prepared = heapq.heappop(pending)
        _commit_one(t1, seq, nid, prepared)
    _check(t1)

    union = state.dag
    extras = {
        "contribution_m0": np.asarray(contribution_rates(union, 0)),
        "contribution_m1": np.asarray(contribution_rates(union, 1)),
        "published": np.asarray(union.published_per_node),
        "behaviors": [n.behavior for n in nodes],
        "dag": union,
    }
    extras.update(backend.extras(union))
    _late_contributions(union, mid_snapshot, extras)
    it_arr, t_arr, a_arr = map(np.asarray, zip(*curve))
    return SimResult(
        backend.name, it_arr, t_arr, a_arr, float(np.mean(lats)),
        state.target_model if state.target_model is not None else params0, extras,
    )


def run_dagfl(
    task,
    nodes: List[SimNode],
    dcfg: DagFLConfig,
    sim: SimConfig,
    global_val: Dict[str, np.ndarray],
    weighted: bool = False,
) -> SimResult:
    return _run_dagfl_events(
        task, nodes, dcfg, sim, global_val, weighted,
        lambda state, commit_fn: _SharedLedger(state, commit_fn),
    )


# ---------------------------------------------------------------------------
# DAG-FL over a gossip overlay (repro.net)
# ---------------------------------------------------------------------------


def _gossip_commit(dag, bank, node_id, t_publish, prepared, seq):
    """Stage-4 commit against a node's LOCAL replica, at a global row.

    The same ``commit_prepared`` body as the shared ledger, addressed by
    ``replica.global_row`` instead of the replica-local count, so every
    replica stores this transaction at the same slot and ``dag.merge`` can
    reconcile by identity.
    """
    slot, new_count = replica_lib.global_row(dag, seq)
    return commit_prepared(
        dag, bank, node_id, t_publish, prepared, slot=slot, new_count=new_count
    )


class _GossipLedger:
    """Per-node replicas over a gossip overlay (repro.net)."""

    name = "dagfl_gossip"

    def __init__(self, state, topology, gossip, partition, mesh=None,
                 bank_gossip=None, obs=None, faults=None, serve=None):
        self.net = gossip_lib.GossipNetwork(
            state.dag, state.bank, topology, gossip, partition, mesh=mesh,
            bank_cfg=bank_gossip, obs_cfg=obs, faults_cfg=faults,
            serve_cfg=serve,
        )
        self.capacity = int(state.dag.publisher.shape[0])
        self.seq = int(state.dag.count)       # genesis consumed sequence 0
        self._commit = _jit_of(_gossip_commit)
        self.approvals_issued = 0
        self.divergence = []
        self.bank_lag = []

    @property
    def bank(self):
        return self.net.bank

    def view(self, node_id):
        # with the bank gossiped this is the node's USABLE view: rows whose
        # model chunks have not arrived are masked out, so Algorithm-2 tip
        # selection — and hence approvals — waits for the payload
        return self.net.read_view(node_id)

    def advance(self, t):
        self.net.advance(t)

    def fetch(self, label, x):
        return self.net._fetch(label, x)

    def on_start(self, node_id, t0, t1):
        # iteration span for the event trace (no-op without telemetry);
        # routes through the device ring under ObsConfig.device_spans
        self.net.trace_span(t0, obs_trace.KIND_PUBLISH, node_id, node_id,
                            t1 - t0)

    def commit(self, node_id, t1, prepared):
        dag_i = self.net.read(node_id)
        # distinct-approval accounting: a credit is "issued" only when this
        # node was not already an approver of the row in its own replica —
        # the same predicate publish_at's crossing scan applies, so in the
        # ideal-wire limit issued == what survives the union exactly
        rows, appr = self.net._fetch(
            "approvals", (prepared.chosen_rows, dag_i.approvers))
        self.approvals_issued += int(
            sum(1 for r in rows if r >= 0 and not appr[r, node_id])
        )
        # wire compression (repro.kernels.delta_codec): encode the commit
        # against the slot's pre-overwrite content, store the DEQUANTIZED
        # wire values (lossy error enters training exactly once, here) and
        # digest the ENCODED pytree so the spoof defense verifies the bytes
        # that actually cross the link. Identity codecs skip all of it —
        # the PR-7 commit path, bitwise.
        slot = self.seq % self.capacity
        codec = (self.net.bank_cfg.codec
                 if self.net.bank_cfg is not None else None)
        if codec is not None and not codec.is_identity:
            base = jax.tree_util.tree_map(lambda b: b[slot], self.net.bank)
            enc = codec.encode(prepared.new_params, base)
            prepared = prepared._replace(
                new_params=codec.decode(enc, base)
            )
        else:
            enc = prepared.new_params
        dag_i, bank = self._commit(
            dag_i, self.net.bank, node_id, jnp.float32(t1), prepared,
            jnp.int32(self.seq),
        )
        self.net.write(node_id, dag_i, bank)
        # transport accounting: the committer holds its own payload's
        # chunks; the ring-reused slot's old content leaves everyone else
        self.net.bank_commit(node_id, slot, enc)
        self.net.trace_span(t1, obs_trace.KIND_COMMIT, node_id, node_id,
                            float(self.seq))
        self.seq += 1

    def union_dag(self):
        return self.net.union()

    def fault_bias(self):
        """(N+1,) log-credit tip-selection bias from digest rejections.

        ``anomaly.rejection_credit`` over the fault layer's cumulative
        rejection matrix: a clean sender's credit is exactly 1.0 (zero
        bias — the honest trajectory is unperturbed), a quarantined
        spoofer's collapses toward the floor, down-weighting its tips in
        Algorithm-2 selection the same way the §VI.B credit extension
        does. The trailing slot covers publisher -1 (genesis). ``None``
        without a fault-state carry."""
        credit = self.net.rejection_credit(label="fault_bias")
        if credit is None:
            return None
        return jnp.log(jnp.concatenate([
            jnp.asarray(credit, jnp.float32), jnp.ones((1,), jnp.float32)
        ]))

    def observe(self, done, t1, union):
        rows = self.net.missing_rows(union, label="observe")
        self.divergence.append((done, float(t1), int(rows.max())))
        if self.net.bank_cfg is not None:
            chunks = self.net.missing_chunks(label="observe")
            self.bank_lag.append((done, float(t1), int(chunks.max())))

    def extras(self, union):
        out = {}
        if self.net.bank_cfg is not None:
            out = {
                # payload transport: chunks still owed vs what the run paid
                "bank_missing_final": self.net.missing_chunks(),
                "bank_bytes_sent": self.net.bytes_sent(),
                "bank_lag_curve": np.asarray(self.bank_lag, dtype=np.float64),
            }
        if self.net.obs_cfg is not None:
            # drained telemetry: metric series, trace, dispatch breakdown
            out["obs"] = self.net.obs_report()
        if self.net.faults_cfg is not None:
            # adversary post-mortem: roles, rejections, quarantine, ASR
            out["fault_report"] = self.net.fault_report()
        sr = self.net.serve_report()
        if sr is not None:
            # inference-load summary: per-node throughput counters plus
            # staleness-at-serve percentiles (repro.net.serve.report)
            out["serve_report"] = sr
        return out | {
            "replicas": self.net.replicas,
            "sync_rounds": self.net.rounds_run,
            "device_calls": self.net.device_calls,
            "dispatch_counts": dict(self.net.dispatch_counts),
            "host_syncs": self.net.host_syncs,
            "sync_counts": dict(self.net.sync_counts),
            "read_calls": self.net.read_calls,
            "events_processed": self.net.events_processed,
            "synced_final": self.net.synced(),
            "missing_rows_final": self.net.missing_rows(union),
            # approval deficit: distinct credits issued by committers vs
            # what survives the union — with the exact approver-set merge
            # the only loss channel left is ring eviction
            "approvals_issued": self.approvals_issued,
            "approvals_in_union": int(
                np.asarray(jnp.sum(union.approval_count * (union.publisher >= 0)))
            ),
            "divergence_curve": np.asarray(self.divergence, dtype=np.float64),
        }


def run_dagfl_gossip(
    task,
    nodes: List[SimNode],
    dcfg: DagFLConfig,
    sim: SimConfig,
    global_val: Dict[str, np.ndarray],
    weighted: bool = False,
    topology: Optional[topo_lib.Topology] = None,
    gossip: Optional[gossip_lib.GossipConfig] = None,
    partition: Optional[gossip_lib.PartitionSchedule] = None,
    mesh=None,
    bank_gossip: Optional[BankGossipConfig] = None,
    engine: Optional[str] = None,
    obs: Optional[ObsConfig] = None,
    faults=None,
    serve=None,
) -> SimResult:
    """DAG-FL where each node runs Algorithm 2 against its own DAG replica.

    ``prepare`` (stages 1-3) reads the node's LOCAL view at iteration start;
    ``commit`` (stage 4) publishes locally; anti-entropy sync ticks are
    interleaved into the event timeline (``GossipNetwork.advance``). The
    external agent E evaluates the union of all replicas — with an ideal
    wire (``sync_period <= 0``, drop 0, connected overlay) this reduces
    exactly to ``run_dagfl``; with finite sync periods, losses, or a
    partition schedule, tip staleness, duplicate approvals across stale
    views, and partition/heal convergence become measurable in ``extras``.
    ``mesh`` (repro.net.mesh) shards the replica set's receiver axis over
    the mesh's "nodes" axis — bitwise the same simulation, run across
    devices.

    ``bank_gossip`` (repro.net.bank) makes MODEL PAYLOAD transport explicit:
    chunk availability gossips alongside the rows, each transfer is charged
    against the overlay's Table-I per-link bandwidth
    (``Topology.bandwidth``), and a node's view only shows transactions
    whose model chunks have arrived — Algorithm-2 approvals wait for the
    payload. With unlimited per-link capacity this is BITWISE the
    ``bank_gossip=None`` run for every round impl and mesh (the chunk step
    is deterministic and leaves the PRNG stream untouched); with Table-I
    budgets, time-to-model-availability (``extras["bank_lag_curve"]``) and
    the byte bill (``extras["bank_bytes_sent"]``) become measurable.

    ``engine`` overrides the transport clock (``GossipConfig.engine``):
    "ticks" is the quantized stride model (the default, bitwise what it
    was); "events" runs the continuous-time engine (``repro.net.events``)
    — sync messages cross each link at its ACTUAL latency and bank chunks
    drain at whole-chunk completion instants. With a uniform per-edge
    delay equal to the sync period the two engines are bitwise identical
    (CI-enforced); heterogeneous latencies make the difference measurable.

    ``obs`` (``repro.obs.ObsConfig``) turns on device-resident telemetry:
    metric accumulators and an event trace ring ride the jitted sync loops
    as pure reads, drained into ``extras["obs"]`` (an ``ObsReport`` —
    Chrome-trace / JSONL export via ``repro.obs.export``). Collection
    never perturbs the trajectory: the obs-on run is bitwise the obs-off
    run (CI-enforced).

    ``faults`` (``repro.net.faults.FaultConfig``) injects Byzantine roles
    into the sync transport — crash/churn windows, eclipse adjacency
    rewrites, selective forwarding, payload spoofing, sybil approval
    inflation — with digest verification + quarantine as the defense.
    ``faults=None`` (and an all-honest config) leaves every path bitwise
    what it was; adversarial runs surface ``extras["fault_report"]`` and
    fold rejection credit into tip selection (``fault_bias``).

    ``serve`` (``repro.net.serve.ServeConfig``) adds per-node Poisson
    inference load to the continuous-time engine: requests arrive at each
    node, batch onto fixed slots, and are answered from the node's
    availability-GATED view — so staleness-at-serve-time is the
    transport's doing. ``serve=None`` and any ``rate<=0`` config leave
    every path bitwise what it was (CI-enforced); serving runs surface
    ``extras["serve_report"]`` (per-node throughput + staleness
    percentiles). Requires ``engine="events"``.
    """
    if topology is None:
        topology = topo_lib.full(len(nodes))
    if gossip is None:
        gossip = gossip_lib.GossipConfig(sync_period=1.0, seed=sim.seed)
    if engine is not None:
        gossip = dataclasses.replace(gossip, engine=engine)
    return _run_dagfl_events(
        task, nodes, dcfg, sim, global_val, weighted,
        lambda state, commit_fn: _GossipLedger(
            state, topology, gossip, partition, mesh=mesh,
            bank_gossip=bank_gossip, obs=obs, faults=faults, serve=serve,
        ),
    )


# ---------------------------------------------------------------------------
# Google FL (synchronous rounds)
# ---------------------------------------------------------------------------


def run_google(
    task, nodes: List[SimNode], dcfg: DagFLConfig, sim: SimConfig,
    global_val: Dict[str, np.ndarray],
) -> SimResult:
    rng = np.random.default_rng(sim.seed)
    lat = LatencyModel.create(dcfg, sim.seed)
    gv = _jb(global_val)
    N, cohort = len(nodes), lat.google_cohort
    params = task.init(jax.random.PRNGKey(sim.seed))
    train = jax.jit(make_epoch_train(task))
    evalf = jax.jit(task.eval_fn)

    t, done, curve, lats = 0.0, 0, [], []
    while done < sim.iterations:
        sel = rng.choice(N, size=cohort, replace=False)
        # shared-medium: cohort downloads then uploads serialize (2*c*tx);
        # training runs in parallel (max d0)
        d0s = [0.0 if nodes[s].behavior == "lazy" else lat.d0(s) for s in sel]
        round_time = 2 * cohort * lat.tx_time() + max(d0s)
        locals_ = []
        for s in sel:
            node = nodes[s]
            if node.behavior == "lazy":
                locals_.append(params)                    # re-uploads the global
            else:
                p, _ = train(params, _jb(node.epoch(sim.steps_per_iter, sim.minibatch)),
                             jax.random.PRNGKey(done + s))
                locals_.append(p)
        params = jax.tree_util.tree_map(
            lambda *xs: sum(x.astype(jnp.float32) for x in xs) / len(xs), *locals_
        )
        t += round_time
        done += cohort
        lats.extend([round_time] * cohort)               # every member waits the round
        if (done // cohort) % max(sim.eval_every // cohort, 1) == 0 or done >= sim.iterations:
            curve.append((done, t, float(evalf(params, gv))))

    it_arr, t_arr, a_arr = map(np.asarray, zip(*curve))
    return SimResult("google", it_arr, t_arr, a_arr, float(np.mean(lats)), params)


# ---------------------------------------------------------------------------
# Asynchronous FL (server-side mixing, Xie et al. [7])
# ---------------------------------------------------------------------------


def run_async(
    task, nodes: List[SimNode], dcfg: DagFLConfig, sim: SimConfig,
    global_val: Dict[str, np.ndarray],
) -> SimResult:
    rng = np.random.default_rng(sim.seed)
    lat = LatencyModel.create(dcfg, sim.seed)
    gv = _jb(global_val)
    N = len(nodes)
    params = task.init(jax.random.PRNGKey(sim.seed))
    train = jax.jit(make_epoch_train(task))
    evalf = jax.jit(task.eval_fn)
    mix = sim.async_mix

    starts = _poisson_starts(rng, dcfg.arrival_rate, sim.iterations)
    curve, lats = [], []
    for i, t0 in enumerate(starts):
        node = nodes[rng.integers(0, N)]
        lazy = node.behavior == "lazy"
        t1 = t0 + lat.async_iteration(node.node_id, lazy=lazy)
        if lazy:
            local = params
        else:
            local, _ = train(params, _jb(node.epoch(sim.steps_per_iter, sim.minibatch)),
                             jax.random.PRNGKey(sim.seed * 7919 + i))
        params = jax.tree_util.tree_map(
            lambda g, l: ((1 - mix) * g.astype(jnp.float32) + mix * l.astype(jnp.float32)).astype(g.dtype),
            params, local,
        )
        lats.append(t1 - t0)
        if (i + 1) % sim.eval_every == 0 or i == sim.iterations - 1:
            curve.append((i + 1, t1, float(evalf(params, gv))))

    it_arr, t_arr, a_arr = map(np.asarray, zip(*curve))
    return SimResult("async", it_arr, t_arr, a_arr, float(np.mean(lats)), params)


# ---------------------------------------------------------------------------
# Block FL (miners + PoW, Kim et al. [3])
# ---------------------------------------------------------------------------


def run_block(
    task, nodes: List[SimNode], dcfg: DagFLConfig, sim: SimConfig,
    global_val: Dict[str, np.ndarray], num_miners: int = 5,
) -> SimResult:
    rng = np.random.default_rng(sim.seed)
    lat = LatencyModel.create(dcfg, sim.seed)
    gv = _jb(global_val)
    N = len(nodes)
    params = task.init(jax.random.PRNGKey(sim.seed))
    train = jax.jit(make_epoch_train(task))
    evalf = jax.jit(task.eval_fn)

    miner_of = {i: i % num_miners for i in range(N)}
    collected: List[List[Any]] = [[] for _ in range(num_miners)]
    first_ts: List[Optional[float]] = [None] * num_miners
    pow_until: List[float] = [0.0] * num_miners          # busy mining until t
    global_acc = float(evalf(params, gv))

    starts = _poisson_starts(rng, dcfg.arrival_rate, sim.iterations)
    curve, lats, dropped = [], [], 0
    for i, t0 in enumerate(starts):
        node = nodes[rng.integers(0, N)]
        m = miner_of[node.node_id]
        lazy = node.behavior == "lazy"
        t1 = t0 + lat.block_iteration(node.node_id, lazy=lazy)
        lats.append(t1 - t0)
        if lazy:
            local = params
        else:
            local, _ = train(params, _jb(node.epoch(sim.steps_per_iter, sim.minibatch)),
                             jax.random.PRNGKey(sim.seed * 104729 + i))

        if t1 < pow_until[m]:
            dropped += 1                                  # miner busy mining: tx lost
        else:
            # miner validates with the full test set (Section V.A.1)
            acc = float(evalf(local, gv))
            if acc >= global_acc - sim.block_margin:
                collected[m].append(local)
                if first_ts[m] is None:
                    first_ts[m] = t1
            # block trigger: 5 tx or 10 s since first
            if collected[m] and (
                len(collected[m]) >= lat.block_collect
                or t1 - (first_ts[m] or t1) >= lat.block_timeout
            ):
                mine = lat.pow_time(rng)
                pow_until[m] = t1 + mine
                # the block extends the chain: previous global is a member of
                # the average (keeps small blocks from thrashing the model)
                stacked = [params] + collected[m]
                params = jax.tree_util.tree_map(
                    lambda *xs: sum(x.astype(jnp.float32) for x in xs) / len(xs), *stacked
                )
                global_acc = float(evalf(params, gv))
                collected[m], first_ts[m] = [], None

        if (i + 1) % sim.eval_every == 0 or i == sim.iterations - 1:
            curve.append((i + 1, t1, global_acc))

    it_arr, t_arr, a_arr = map(np.asarray, zip(*curve))
    return SimResult(
        "block", it_arr, t_arr, a_arr, float(np.mean(lats)), params,
        {"dropped": dropped},
    )


SYSTEMS: Dict[str, Callable] = {
    "dagfl": run_dagfl,
    "dagfl_gossip": run_dagfl_gossip,
    "google": run_google,
    "async": run_async,
    "block": run_block,
}
