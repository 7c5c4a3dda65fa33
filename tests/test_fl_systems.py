"""Integration: the five FL systems run and produce sane results (small scale)."""
import numpy as np
import pytest

from repro.fl.experiments import default_dagfl_config, make_cnn_setup, make_lstm_setup
from repro.fl.systems import (
    SYSTEMS,
    SimConfig,
    run_async,
    run_block,
    run_dagfl,
    run_dagfl_gossip,
    run_google,
)
from repro.net import topology as topo
from repro.net.gossip import GossipConfig, PartitionSchedule


@pytest.fixture(scope="module")
def cnn_setup():
    task, nodes, gval, gen = make_cnn_setup(num_nodes=16, seed=0)
    dcfg = default_dagfl_config(num_nodes=16)
    sim = SimConfig(iterations=60, eval_every=20, seed=0)
    return task, nodes, gval, dcfg, sim


@pytest.mark.parametrize(
    "runner", [run_dagfl, run_dagfl_gossip, run_async, run_block, run_google]
)
def test_system_runs_and_improves_or_stays_finite(cnn_setup, runner):
    task, nodes, gval, dcfg, sim = cnn_setup
    res = runner(task, nodes, dcfg, sim, gval)
    assert len(res.accs) >= 2
    assert np.all(np.isfinite(res.accs))
    assert res.avg_latency > 0
    assert res.times[-1] > 0


def test_gossip_registered_in_systems():
    assert SYSTEMS["dagfl_gossip"] is run_dagfl_gossip


@pytest.fixture(scope="module")
def ideal_wire_base():
    n, dcfg = 12, default_dagfl_config(num_nodes=12)
    sim = SimConfig(iterations=40, eval_every=10, seed=0)
    task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
    return run_dagfl(task, nodes, dcfg, sim, gval)


@pytest.mark.parametrize("impl", ["fused", "scan"])
def test_gossip_ideal_wire_recovers_shared_ledger(ideal_wire_base, impl):
    """sync period -> 0, drop 0, connected overlay: the gossip system's
    accuracy curve must match run_dagfl within noise (here: exactly, same
    RNG streams + deterministic CPU ops) — under both the reference scan
    round and the fused kernel round."""
    n, dcfg = 12, default_dagfl_config(num_nodes=12)
    sim = SimConfig(iterations=40, eval_every=10, seed=0)
    base = ideal_wire_base
    task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)   # fresh node RNGs
    ideal = run_dagfl_gossip(
        task, nodes, dcfg, sim, gval,
        topology=topo.full(n),
        gossip=GossipConfig(sync_period=0.0, seed=0, impl=impl),
    )
    np.testing.assert_allclose(ideal.accs, base.accs, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(ideal.times, base.times, rtol=1e-9)
    # serialized commits: no duplicate-approval deficit in the ideal limit
    assert ideal.extras["approvals_issued"] == ideal.extras["approvals_in_union"]


@pytest.mark.parametrize("runner", [run_dagfl, run_dagfl_gossip])
def test_zero_iteration_run_returns_empty_curve(runner):
    """Regression: iterations=0 used to crash on the trailing eval (its
    completion time never got bound); now it returns an empty-curve result."""
    n = 6
    task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
    dcfg = default_dagfl_config(num_nodes=n)
    sim = SimConfig(iterations=0, eval_every=10, seed=0)
    res = runner(task, nodes, dcfg, sim, gval)
    assert len(res.iters) == len(res.times) == len(res.accs) == 0
    assert res.avg_latency == 0.0
    assert res.acc_at(100) == 0.0
    assert len(res.extras["behaviors"]) == n


@pytest.mark.parametrize("engine,device_calls", [("events", 20), ("ticks", 11)])
def test_gossip_reads_two_replicas_per_iteration(engine, device_calls):
    """Each committed iteration reads its node's replica twice (the view at
    start, the replica at commit), counted in ``read_calls`` and not in
    ``device_calls``, which keeps the count it had with eager reads."""
    n = 6
    task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
    res = run_dagfl_gossip(
        task, nodes, default_dagfl_config(num_nodes=n),
        SimConfig(iterations=10, eval_every=5, seed=0), gval,
        topology=topo.full(n, link_latency=0.5),
        gossip=GossipConfig(sync_period=1.0, seed=0), engine=engine,
    )
    committed = int(np.sum(res.extras["published"][:-1]))
    assert committed == 10
    assert res.extras["read_calls"] == 2 * committed
    assert res.extras["device_calls"] == device_calls


def test_gossip_stale_overlay_diverges_and_reports_metrics():
    n, dcfg = 12, default_dagfl_config(num_nodes=12)
    sim = SimConfig(iterations=40, eval_every=10, seed=0)
    task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
    res = run_dagfl_gossip(
        task, nodes, dcfg, sim, gval,
        topology=topo.ring(n), gossip=GossipConfig(sync_period=4.0, seed=0),
    )
    assert np.all(np.isfinite(res.accs))
    assert res.extras["sync_rounds"] > 0
    # a slow ring leaves some replicas behind the union view at the end
    assert res.extras["missing_rows_final"].max() > 0
    assert res.extras["divergence_curve"].shape[1] == 3


def test_gossip_partition_runs_and_heals_visibility():
    """A mid-run partition splits the overlay; after healing, gossip pulls
    every replica back to the union view."""
    n, dcfg = 10, default_dagfl_config(num_nodes=10)
    sim = SimConfig(iterations=30, eval_every=10, seed=0)
    task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
    part = PartitionSchedule(assignment=topo.split_halves(n), t_start=5.0, t_end=20.0)
    res = run_dagfl_gossip(
        task, nodes, dcfg, sim, gval,
        topology=topo.full(n), gossip=GossipConfig(sync_period=0.5, seed=0),
        partition=part,
    )
    assert np.all(np.isfinite(res.accs))
    # replicas reconverge once the schedule heals and ticks keep flowing
    from repro.net import replica as replica_lib
    from repro.net.gossip import GossipNetwork

    rs = res.extras["replicas"]
    net = GossipNetwork(
        replica_lib.read_replica(rs, 0), rs.bank, topo.full(n),
        GossipConfig(sync_period=0.5, seed=1),
    )
    net.replicas = rs
    assert net.converge(at_time=1e9)
    assert bool(replica_lib.replicas_synced(net.replicas.dags))


def test_latency_ordering_matches_table2(cnn_setup):
    """Google's synchronous rounds are the slowest per iteration (Table II)."""
    task, nodes, gval, dcfg, sim = cnn_setup
    dag = run_dagfl(task, nodes, dcfg, sim, gval)
    goo = run_google(task, nodes, dcfg, sim, gval)
    asy = run_async(task, nodes, dcfg, sim, gval)
    assert goo.avg_latency > dag.avg_latency
    assert goo.avg_latency > asy.avg_latency


def test_dagfl_contribution_extras(cnn_setup):
    task, nodes, gval, dcfg, sim = cnn_setup
    res = run_dagfl(task, nodes, dcfg, sim, gval)
    assert "contribution_m0" in res.extras
    assert len(res.extras["behaviors"]) == len(nodes)


def test_lstm_task_systems_run():
    task, nodes, gval, corpus = make_lstm_setup(num_nodes=10, seed=0)
    dcfg = default_dagfl_config(num_nodes=10, task="lstm")
    sim = SimConfig(iterations=20, eval_every=10, seed=0, minibatch=8,
                    steps_per_iter=2, val_size=8)
    res = run_dagfl(task, nodes, dcfg, sim, gval)
    assert np.all(np.isfinite(res.accs))
