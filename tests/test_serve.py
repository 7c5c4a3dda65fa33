"""Inference serving on the event engine (`repro.net.serve`).

Pins the acceptance invariants of the serving layer:

* ZERO-RATE DEGENERATE LIMIT: ``serve=None`` and ``ServeConfig(rate=0.0)``
  are BITWISE the PR-8 run — replicas, bank state, and PRNG key alike —
  across round impls, overlays, the bank, faulted arms, and partitions
  (the obs=None / faults=None / codec=None pattern: off is not a branch,
  off is the literal pre-serve program);
* Poisson arrivals are reproducible pure functions of (seed, node, count)
  with no host RNG — the engine's counters match an independent host
  replay exactly, and the long-horizon arrival counts match the
  configured rate (property-tested);
* service conserves requests (arrived = served + queued + inflight +
  dropped), batches respect the slot cap, and the staleness-at-admit
  samples are measured against the availability-GATED view — a
  constrained wire shows up as positive staleness, an idle ledger as 0;
* the counters export through ``repro.obs`` (requests_served /
  serve_staleness series, KIND_INFER trace records, "infer" Chrome-trace
  slices) and through ``run_dagfl_gossip(serve=...)`` ->
  ``extras["serve_report"]``;
* every node id in tests/known_failures.txt still collects (a renamed
  test would silently disable its strict xfail).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import dag as dag_lib
from repro.net import events as events_lib
from repro.net import faults as faults_lib
from repro.net import gossip as gossip_lib
from repro.net import replica as replica_lib
from repro.net import serve as serve_lib
from repro.net import topology as topo
from repro.net.bank import BankGossipConfig
from repro.net.faults import FaultConfig
from repro.net.serve import ServeConfig

CAP, K = 32, 2
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
IMPLS = ["fused", "scan"]


def genesis(num_nodes):
    d = dag_lib.empty_dag(CAP, K, num_nodes + 1)
    return dag_lib.publish(
        d, jnp.asarray(num_nodes, jnp.int32), jnp.float32(0.0),
        jnp.full((K,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(0, jnp.int32),
    )


def make_net(top, serve=None, sync_period=1.0, partition=None, seed=0,
             impl="fused", bank_cfg=None, obs_cfg=None, faults_cfg=None):
    return gossip_lib.GossipNetwork(
        genesis(top.num_nodes), bank=jnp.zeros((CAP, 8)), top=top,
        cfg=gossip_lib.GossipConfig(sync_period=sync_period, seed=seed,
                                    impl=impl, engine="events"),
        partition=partition, bank_cfg=bank_cfg, obs_cfg=obs_cfg,
        faults_cfg=faults_cfg, serve_cfg=serve,
    )


def publish_on(net, node, seq, t, params=None):
    d = replica_lib.publish_local(
        net.read(node), seq, jnp.asarray(node, jnp.int32), jnp.float32(t),
        jnp.full((K,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(seq % CAP, jnp.int32),
    )
    net.write(node, d)
    if net.bank_cfg is not None:
        if params is None:
            params = jnp.full((8,), float(seq))
        net.bank_commit(node, seq % CAP, params)


def assert_dags_equal(a, b, msg=""):
    for name in dag_lib.DagState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{msg}: dag field {name}",
        )


def assert_nets_bitwise(a, b, msg=""):
    assert_dags_equal(a.replicas.dags, b.replicas.dags, msg)
    np.testing.assert_array_equal(
        np.asarray(a._key), np.asarray(b._key), err_msg=f"{msg}: PRNG key"
    )
    if a.bank_cfg is not None:
        for name in a.replicas.bank_state._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a.replicas.bank_state, name)),
                np.asarray(getattr(b.replicas.bank_state, name)),
                err_msg=f"{msg}: bank field {name}",
            )


# ---------------------------------------------------------------------------
# Satellite: zero-rate degenerate limit — bitwise the PR-8 program
# ---------------------------------------------------------------------------


def _run_arm(serve, impl, arm, seed=0, obs_cfg=None):
    n = 6
    bank_cfg = (BankGossipConfig(chunks_per_slot=2)
                if arm in ("bank", "bank_faults", "bank_partition") else None)
    faults_cfg = None
    if arm in ("faults", "bank_faults"):
        roles = (faults_lib.ROLE_SPOOF if bank_cfg is not None
                 else faults_lib.ROLE_SELECTIVE,) + (0,) * (n - 1)
        faults_cfg = FaultConfig(roles=roles)
    partition = None
    if arm == "bank_partition":
        partition = gossip_lib.PartitionSchedule(
            assignment=topo.split_halves(n), t_start=3.0, t_end=9.0
        )
    top = (topo.ring(n, link_latency=0.7) if arm == "faults"
           else topo.full(n, link_latency=1.0))
    net = make_net(top, serve=serve, impl=impl, seed=seed,
                   bank_cfg=bank_cfg, faults_cfg=faults_cfg,
                   partition=partition, obs_cfg=obs_cfg)
    for i in range(n):
        publish_on(net, i, 1 + i, 0.25 + 0.5 * i)
    net.advance(7.5)
    for i in range(n):
        publish_on(net, i, 1 + n + i, 8.0 + 0.25 * i)
    net.advance(15.0)
    return net


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize(
    "arm", ["plain", "bank", "faults", "bank_faults", "bank_partition"]
)
def test_zero_rate_bitwise_degenerate_limit(impl, arm):
    """serve=None and rate=0.0 both run the literal PR-8 program: same
    dags, same bank state, same PRNG key, for every engine arm."""
    base = _run_arm(None, impl, arm)
    zero = _run_arm(ServeConfig(rate=0.0), impl, arm)
    assert_nets_bitwise(base, zero, f"{impl}/{arm}")
    assert base.serve_report() is None and zero.serve_report() is None


def test_zero_rate_compiles_the_identical_program():
    """The static key maps rate<=0 to None, so a rate-0 net doesn't just
    agree numerically — it reuses the SAME cached jitted program object."""
    assert serve_lib.serve_key(None) is None
    assert serve_lib.serve_key(ServeConfig(rate=0.0)) is None
    assert serve_lib.serve_key(ServeConfig(rate=-1.0)) is None
    cfg = ServeConfig(rate=2.0)
    assert serve_lib.serve_key(cfg) is cfg
    # a rate-0 net takes the serve-free dispatch branch entirely: no
    # effective config, no INFER slots appended to the event queue
    top = topo.full(3, link_latency=1.0)
    zero = make_net(top, serve=ServeConfig(rate=0.0))
    none = make_net(top, serve=None)
    live = make_net(top, serve=cfg)
    assert zero._serve is None and none._serve is None
    assert zero._equeue.time.shape == none._equeue.time.shape
    assert live._serve is cfg
    assert (live._equeue.time.shape[0]
            == none._equeue.time.shape[0] + 2 * 3)
    assert int(jnp.sum(live._equeue.kind == events_lib.KIND_INFER)) == 6


def test_validate_serve_rejects_bad_configs():
    top = topo.full(4)
    with pytest.raises(ValueError, match="events"):
        gossip_lib.GossipNetwork(
            genesis(4), bank=jnp.zeros((CAP, 8)), top=top,
            cfg=gossip_lib.GossipConfig(sync_period=1.0, engine="ticks"),
            serve_cfg=ServeConfig(rate=1.0),
        )
    # rate-0 on the tick engine is fine: it degenerates to no serving
    gossip_lib.GossipNetwork(
        genesis(4), bank=jnp.zeros((CAP, 8)), top=top,
        cfg=gossip_lib.GossipConfig(sync_period=1.0, engine="ticks"),
        serve_cfg=ServeConfig(rate=0.0),
    )
    for bad in (ServeConfig(rate=1.0, slots=0),
                ServeConfig(rate=1.0, queue_cap=0),
                ServeConfig(rate=1.0, service_time=0.0)):
        with pytest.raises(ValueError):
            make_net(top, serve=bad)


# ---------------------------------------------------------------------------
# Arrival process: deterministic fold_in branch, no host RNG
# ---------------------------------------------------------------------------


def test_engine_arrivals_match_host_replay():
    """The engine's per-node arrival counters equal an independent host
    replay of the same (seed, node, count) fold_in chain — arrivals are a
    pure function of the config, not of engine scheduling."""
    cfg = ServeConfig(rate=2.0, service_time=0.05)
    seed, horizon, n = 3, 25.0, 4
    net = make_net(topo.full(n, link_latency=1.0), serve=cfg, seed=seed)
    net.advance(horizon)
    rep = net.serve_report()
    for node in range(n):
        expect = len(serve_lib.arrival_times(seed, cfg, node, horizon))
        assert int(rep["arrivals"][node]) == expect, f"node {node}"
    # and the whole report replays bitwise on a fresh identical net
    net2 = make_net(topo.full(n, link_latency=1.0), serve=cfg, seed=seed)
    net2.advance(horizon)
    rep2 = net2.serve_report()
    np.testing.assert_array_equal(rep["arrivals"], rep2["arrivals"])
    np.testing.assert_array_equal(rep["requests_served"],
                                  rep2["requests_served"])
    np.testing.assert_array_equal(rep["staleness_samples"],
                                  rep2["staleness_samples"])


def test_priced_drain_rearm_makes_strict_progress():
    """Regression: a priced drain's re-arm instant is computed so accrued
    credit EXACTLY completes a chunk, so every completion sits within f32
    rounding of the chunk boundary. When the rounding left ``credit`` just
    under ``chunk_bytes``, the re-arm collapsed onto its own instant and
    the advance livelocked against ``max_events_per_advance``, silently
    starving every event queued behind the spinning drain (arrivals
    included). The strict-progress clamp (``events.py``) pins: no advance
    leaves a valid past-due event behind, and the arrival counters still
    equal the host Poisson replay under heavy drain churn."""
    n, seed = 6, 0
    cfg = ServeConfig(rate=2.0, service_time=0.05)
    net = make_net(
        topo.ring(n, bandwidth=1e7), serve=cfg, seed=seed,
        bank_cfg=BankGossipConfig(chunks_per_slot=4, slot_bytes=7e6),
    )
    t = 0.0
    for k in range(12):
        publish_on(net, k % n, 1 + k, t,
                   params=jnp.full((8,), 1.0 + 0.37 * k))
        t += 0.937                     # irregular accrual windows
        net.advance(t)
        qt = np.asarray(net._equeue.time)
        qv = np.asarray(net._equeue.valid)
        stranded = qv & (qt <= t)
        assert not stranded.any(), (
            f"advance({t:.3f}) left due events at {qt[stranded]}"
        )
    rep = net.serve_report()
    for node in range(n):
        expect = len(serve_lib.arrival_times(seed, cfg, node, t))
        assert int(rep["arrivals"][node]) == expect, f"node {node}"


@pytest.mark.parametrize("obs", [None, "obs"])
@pytest.mark.parametrize("arm", ["plain", "bank", "faults", "bank_faults"])
def test_serve_randomness_leaves_main_key_untouched(arm, obs):
    """INFER batches never split the main PRNG key: the key trajectory of
    a serving run equals the serve-free run over the same net events, in
    every layer combination of the event advance, telemetry on or off."""
    from repro.obs import ObsConfig

    obs_cfg = ObsConfig() if obs else None
    a = _run_arm(None, "fused", arm, obs_cfg=obs_cfg)
    b = _run_arm(ServeConfig(rate=3.0), "fused", arm, obs_cfg=obs_cfg)
    assert b.serve_report()["served_total"] > 0
    np.testing.assert_array_equal(np.asarray(a._key), np.asarray(b._key))
    assert_dags_equal(a.replicas.dags, b.replicas.dags, "serve-on vs off")
    if a.bank_cfg is not None:
        assert_nets_bitwise(a, b, f"{arm}: serve-on vs off")
    if a._fstate is not None:
        for name in a._fstate._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a._fstate, name)),
                np.asarray(getattr(b._fstate, name)),
                err_msg=f"{arm}: fault field {name}",
            )


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), node=st.integers(0, 7),
       rate=st.sampled_from([0.5, 1.0, 2.0, 5.0]))
def test_property_poisson_rate_and_reproducibility(seed, node, rate):
    """Property: long-horizon arrival counts match the configured rate
    within Poisson bounds, and the sequence replays exactly per
    (seed, node)."""
    cfg = ServeConfig(rate=rate)
    horizon = 200.0 / rate                   # ~200 expected arrivals
    times = serve_lib.arrival_times(seed, cfg, node, horizon)
    mean = rate * horizon
    assert abs(len(times) - mean) <= 6.0 * np.sqrt(mean) + 3.0
    assert np.all(np.diff(times) > 0)        # strictly increasing
    again = serve_lib.arrival_times(seed, cfg, node, horizon)
    np.testing.assert_array_equal(times, again)
    # a different node draws a different stream (same seed)
    other = serve_lib.arrival_times(seed, cfg, (node + 1) % 8, horizon)
    assert len(other) != len(times) or not np.array_equal(times, other)


# ---------------------------------------------------------------------------
# Service semantics: conservation, batching, gated staleness
# ---------------------------------------------------------------------------


def _conserve(rep):
    lhs = rep["arrivals"]
    rhs = rep["requests_served"] + rep["queued"] + rep["inflight"] + \
        rep["dropped"]
    np.testing.assert_array_equal(lhs, rhs)


@pytest.mark.parametrize("bank", [False, True])
def test_serve_counters_conserve_and_batch_cap(bank):
    n = 4
    cfg = ServeConfig(rate=4.0, slots=3, service_time=0.2, queue_cap=8)
    bank_cfg = BankGossipConfig(chunks_per_slot=2) if bank else None
    net = make_net(topo.full(n, link_latency=1.0), serve=cfg,
                   bank_cfg=bank_cfg)
    for i in range(n):
        publish_on(net, i, 1 + i, 0.5 * i)
    net.advance(30.0)
    rep = net.serve_report()
    assert rep["served_total"] > 0
    _conserve(rep)
    # no batch exceeds the slot cap: served + inflight per admitted batch
    assert np.all(rep["batches"] > 0)
    assert np.all(rep["requests_served"] + rep["inflight"]
                  <= rep["batches"] * cfg.slots)
    # staleness samples were taken at admit instants, one per batch
    assert rep["samples"] + rep["samples_dropped"] == int(
        rep["batches"].sum()
    )
    assert np.all(rep["staleness_samples"] >= 0)
    assert np.isfinite(rep["staleness_p50"])


def test_queue_cap_drops_under_overload():
    """A service time far above the inter-arrival gap overloads the node:
    the queue saturates and the overflow is counted dropped, not lost."""
    n = 2
    cfg = ServeConfig(rate=10.0, slots=1, service_time=5.0, queue_cap=4)
    net = make_net(topo.full(n, link_latency=1.0), serve=cfg)
    net.advance(40.0)
    rep = net.serve_report()
    _conserve(rep)
    assert rep["dropped_total"] > 0
    assert np.all(rep["queued"] <= cfg.queue_cap)


def test_staleness_is_gated_by_chunk_availability():
    """With a constrained wire the serve-time staleness sees rows whose
    METADATA arrived but whose chunks did not — the gated view lags until
    payload lands, so positive staleness samples must appear even though
    row gossip alone would have converged."""
    n = 4
    cfg = ServeConfig(rate=3.0, service_time=0.05)
    slow = topo.full(n, link_latency=1.0, bandwidth=64.0)   # bits/s: ~slow
    net = make_net(slow, serve=cfg,
                   bank_cfg=BankGossipConfig(chunks_per_slot=2))
    for i in range(n):
        publish_on(net, i, 1 + i, 0.25)
    net.advance(20.0)
    rep = net.serve_report()
    assert rep["served_total"] > 0
    assert rep["staleness_max"] > 0
    # the same run over an unconstrained wire serves fresh views at the
    # tail (payload keeps up with metadata)
    fast = topo.full(n, link_latency=1.0)
    net2 = make_net(fast, serve=cfg,
                    bank_cfg=BankGossipConfig(chunks_per_slot=2))
    for i in range(n):
        publish_on(net2, i, 1 + i, 0.25)
    net2.advance(20.0)
    rep2 = net2.serve_report()
    tail = rep2["staleness_samples"][-max(1, rep2["samples"] // 4):]
    assert tail.max() <= rep["staleness_max"]
    assert tail.max() == 0


# ---------------------------------------------------------------------------
# Export: obs series, trace records, systems plumbing
# ---------------------------------------------------------------------------


def test_obs_series_and_infer_trace():
    from repro import obs as obs_lib

    n = 4
    net = make_net(topo.full(n, link_latency=1.0),
                   serve=ServeConfig(rate=3.0),
                   bank_cfg=BankGossipConfig(chunks_per_slot=2),
                   obs_cfg=obs_lib.ObsConfig())
    for i in range(n):
        publish_on(net, i, 1 + i, 0.5 * i)
    net.advance(15.0)
    rep = net.obs_report()
    served = rep.series["requests_served"]
    assert served.shape[1] == n
    assert np.all(np.diff(served, axis=0) >= 0)       # cumulative counters
    assert served[-1].sum() > 0
    stale = rep.series["serve_staleness"]
    assert np.all(stale >= -1)
    assert np.any(stale >= 0)                          # admits were sampled
    kinds = set(np.unique(rep.trace["kind"]).tolist())
    assert obs_lib.KIND_INFER in kinds
    # the infer records are node-diagonal with the batch size as arg
    m = rep.trace["kind"] == obs_lib.KIND_INFER
    np.testing.assert_array_equal(rep.trace["src"][m], rep.trace["dst"][m])
    assert np.all(rep.trace["arg"][m] >= 1)
    tr = obs_lib.chrome_trace(rep)
    names = {e["name"] for e in tr["traceEvents"]}
    assert "infer" in names
    # obs collection never perturbs the serve counters
    net2 = make_net(topo.full(n, link_latency=1.0),
                    serve=ServeConfig(rate=3.0),
                    bank_cfg=BankGossipConfig(chunks_per_slot=2))
    for i in range(n):
        publish_on(net2, i, 1 + i, 0.5 * i)
    net2.advance(15.0)
    np.testing.assert_array_equal(net.serve_report()["requests_served"],
                                  net2.serve_report()["requests_served"])
    assert_nets_bitwise(net, net2, "obs-on vs obs-off serving run")


def test_run_dagfl_gossip_serve_report_and_zero_rate():
    """End to end: serve=... surfaces extras["serve_report"]; rate 0 is
    the literal no-serve run (same accuracy curve, no report)."""
    from repro.fl.experiments import default_dagfl_config, make_cnn_setup
    from repro.fl.systems import SimConfig, run_dagfl_gossip

    n = 6
    dcfg = default_dagfl_config(num_nodes=n)
    sim = SimConfig(iterations=16, eval_every=8, seed=0)

    def run(serve):
        task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
        return run_dagfl_gossip(
            task, nodes, dcfg, sim, gval,
            topology=topo.full(n, link_latency=0.5),
            gossip=gossip_lib.GossipConfig(sync_period=1.0, seed=0),
            engine="events", serve=serve,
        )

    base = run(None)
    zero = run(ServeConfig(rate=0.0))
    served = run(ServeConfig(rate=2.0, service_time=0.05))
    assert "serve_report" not in base.extras
    assert "serve_report" not in zero.extras
    np.testing.assert_array_equal(base.accs, zero.accs)
    np.testing.assert_array_equal(base.times, zero.times)
    sr = served.extras["serve_report"]
    assert sr["served_total"] > 0
    assert np.isfinite(sr["staleness_p50"])
    # serving is a pure reader of the ledger: training is unperturbed
    np.testing.assert_array_equal(base.accs, served.accs)


# ---------------------------------------------------------------------------
# Satellite: known_failures.txt hygiene
# ---------------------------------------------------------------------------


def test_known_failures_ids_still_collect():
    """Every node id in tests/known_failures.txt must still exist — a
    renamed or deleted test would silently disable its strict xfail."""
    path = os.path.join(REPO, "tests", "known_failures.txt")
    with open(path) as f:
        ids = [ln.split("#", 1)[0].strip() for ln in f]
    ids = [i for i in ids if i]
    assert ids, "known_failures.txt unexpectedly empty"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-p", "no:cacheprovider", *ids],
        cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, (
        "stale node id(s) in tests/known_failures.txt — update the list "
        "alongside the rename/delete:\n" + proc.stdout + proc.stderr
    )
