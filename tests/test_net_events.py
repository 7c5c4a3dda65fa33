"""Continuous-time event engine (`repro.net.events`).

Pins the acceptance invariants of the engine:

* the ``event_pop`` Pallas kernel is bitwise its pure-lax oracle
  (property-tested over adversarial tie patterns);
* DEGENERATE-LIMIT EQUIVALENCE: with a uniform deterministic per-edge
  delay equal to the sync period (and, for the e2e form, iteration
  completions arriving through the same host driver), the event engine's
  merge sequence — dags, bank state, and PRNG key alike — is BITWISE the
  ``engine="ticks"`` fused path, property-tested over overlays, losses,
  partitions, and interleaved publishes;
* heterogeneous latencies depart in the honest direction: fast links
  deliver before the first tick, slow links at their true cadence, and
  bank chunk-drains recover the bandwidth the stride model forfeits;
* the in-system §IV simulation reproduces the Eq. (4) equilibrium on a
  well-connected overlay and responds to h as the closed form says.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import dag as dag_lib
from repro.core import stability
from repro.configs.base import DagFLConfig
from repro.kernels.event_pop import event_pop_pallas
from repro.kernels import ref as kernel_ref
from repro.net import events as events_lib
from repro.net import gossip as gossip_lib
from repro.net import replica as replica_lib
from repro.net import topology as topo
from repro.net.bank import BankGossipConfig

CAP, K = 32, 2
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Kernel layer: queue-head reduction
# ---------------------------------------------------------------------------


def test_event_pop_ref_tie_breaks():
    t = jnp.asarray([2.0, 1.0, 1.0, 1.0, 1.0])
    k = jnp.asarray([0, 1, 0, 0, 0], jnp.int32)
    s = jnp.asarray([0, 1, 7, 3, 5], jnp.int32)
    v = jnp.asarray([True, True, True, True, True])
    idx, found = kernel_ref.event_pop_ref(t, k, s, v)
    assert bool(found) and int(idx) == 3      # min time, then kind, then seq
    # invalidate the winner: next head is the seq-5 slot
    v = v.at[3].set(False)
    idx, _ = kernel_ref.event_pop_ref(t, k, s, v)
    assert int(idx) == 4
    # nothing valid: found False, idx 0
    idx, found = kernel_ref.event_pop_ref(t, k, s, jnp.zeros(5, bool))
    assert not bool(found) and int(idx) == 0


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), q=st.integers(1, 2600),
       block_q=st.sampled_from([1024, 2048, 16384]))
def test_property_event_pop_pallas_matches_ref(seed, q, block_q):
    """Property: kernel == oracle, including duplicate (time, kind, seq)
    keys across slabs (first-slot tie-break), padded tails and
    all-invalid queues."""
    rng = np.random.default_rng(seed)
    t = rng.choice([0.25, 1.0, 1.5, 7.75], q).astype(np.float32)
    k = rng.integers(0, 4, q).astype(np.int32)
    s = rng.integers(0, 6, q).astype(np.int32)
    v = rng.random(q) < rng.choice([0.0, 0.01, 0.5])
    args = (jnp.asarray(t), jnp.asarray(k), jnp.asarray(s), jnp.asarray(v))
    ri, rf = kernel_ref.event_pop_ref(*args)
    pi, pf = event_pop_pallas(*args, block_q=block_q, interpret=True)
    assert bool(rf) == bool(pf)
    assert int(ri) == int(pi)


def test_delivery_intervals_replace_strides():
    """The interval IS the latency — not ceil(latency/period)*period — with
    zero-latency links on the protocol period."""
    top = topo.ring(4, link_latency=3.7)
    iv = events_lib.delivery_intervals(top, 1.0)
    assert np.allclose(iv[top.adjacency], 3.7)
    top0 = topo.ring(4)
    iv0 = events_lib.delivery_intervals(top0, 1.0)
    assert np.allclose(iv0[top0.adjacency], 1.0)
    assert np.all(np.isinf(iv0[~top0.adjacency]))


# ---------------------------------------------------------------------------
# GossipNetwork engine="events": semantics
# ---------------------------------------------------------------------------


def genesis(num_nodes):
    d = dag_lib.empty_dag(CAP, K, num_nodes + 1)
    return dag_lib.publish(
        d, jnp.asarray(num_nodes, jnp.int32), jnp.float32(0.0),
        jnp.full((K,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(0, jnp.int32),
    )


def make_net(top, engine="events", sync_period=1.0, partition=None, seed=0,
             impl="fused", bank_cfg=None):
    return gossip_lib.GossipNetwork(
        genesis(top.num_nodes), bank=jnp.zeros((CAP, 8)), top=top,
        cfg=gossip_lib.GossipConfig(sync_period=sync_period, seed=seed,
                                    impl=impl, engine=engine),
        partition=partition, bank_cfg=bank_cfg,
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
            err_msg=f"{msg}{name}",
        )


def test_fast_links_deliver_before_the_tick():
    """A 0.5 s link delivers at 0.5 s; the stride model waits for the 1 s
    tick — THE semantic the event engine exists for."""
    tick_net = make_net(topo.ring(6, link_latency=0.5), engine="ticks")
    ev_net = make_net(topo.ring(6, link_latency=0.5), engine="events")
    publish_on(tick_net, 0, 1, 0.1)
    publish_on(ev_net, 0, 1, 0.1)
    tick_net.advance(0.6)
    ev_net.advance(0.6)
    assert (tick_net.missing_rows() > 0).sum() == 5      # nothing until t=1
    assert (ev_net.missing_rows() > 0).sum() == 3        # neighbors heard
    ev_net.advance(1.0)                                  # second hop at 1.0
    assert (ev_net.missing_rows() > 0).sum() == 1


def test_slow_links_fire_at_true_cadence():
    """latency 1.5, period 1: the stride model quantizes to every 2nd tick
    (hops at t=1, 3, 5); events deliver at 1.5, 3.0, 4.5."""
    net = make_net(topo.ring(8, link_latency=1.5), engine="events")
    publish_on(net, 0, 1, 0.1)
    net.advance(1.4)
    assert (net.missing_rows() > 0).sum() == 7
    net.advance(1.5)
    assert (net.missing_rows() > 0).sum() == 5
    net.advance(3.0)
    assert (net.missing_rows() > 0).sum() == 3
    net.advance(4.5)
    assert (net.missing_rows() > 0).sum() == 1


def test_events_ideal_wire_routes_to_converge():
    net = make_net(topo.ring(6, link_latency=2.5), engine="events",
                   sync_period=0.0)
    publish_on(net, 0, 1, 0.5)
    net.advance(1.0)
    assert net.synced()


def test_events_full_drop_blocks_everything():
    net = make_net(topo.ring(6, drop=1.0, link_latency=1.0), engine="events")
    publish_on(net, 0, 1, 0.5)
    net.advance(10.0)
    assert (net.missing_rows() > 0).sum() == 5


def test_events_mesh_not_supported():
    from repro.net import mesh as mesh_lib
    if jax.device_count() < 2:
        pytest.skip("needs >1 device to build a mesh")
    with pytest.raises(NotImplementedError):
        make_net_mesh = gossip_lib.GossipNetwork(
            genesis(8), bank=jnp.zeros((CAP, 8)), top=topo.ring(8),
            cfg=gossip_lib.GossipConfig(engine="events"),
            mesh=mesh_lib.make_gossip_mesh(nodes=2, model=1),
        )


def test_events_mesh_rejected_in_subprocess():
    """Runs on every lane: forces 8 host devices in a child process and
    checks that engine='events' + mesh is rejected — the event queue is not
    mesh-sharded yet (ROADMAP follow-up), and a mesh-aware regression that
    silently accepted the combination would otherwise only fail the
    8-device CI lane."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.core import dag as dag_lib
        from repro.net import gossip as G, mesh as M
        from repro.net import topology as topo
        assert jax.device_count() == 8, jax.device_count()
        CAP, K = 32, 2
        d = dag_lib.empty_dag(CAP, K, 9)
        d = dag_lib.publish(d, jnp.asarray(8, jnp.int32), jnp.float32(0.0),
            jnp.full((K,), dag_lib.NO_TX, jnp.int32), jnp.float32(0.5),
            jnp.float32(0.0), jnp.asarray(0, jnp.int32))
        try:
            G.GossipNetwork(d, bank=jnp.zeros((CAP, 8)), top=topo.ring(8),
                cfg=G.GossipConfig(engine="events"),
                mesh=M.make_gossip_mesh(nodes=2, model=4))
        except NotImplementedError:
            print("OK")
        else:
            raise SystemExit("engine='events' + mesh was accepted")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "OK" in out.stdout


def test_unknown_engine_rejected():
    with pytest.raises(ValueError):
        make_net(topo.ring(4), engine="heap")


def test_events_partition_suppresses_and_heals():
    part = gossip_lib.PartitionSchedule(
        assignment=topo.split_halves(6), t_start=0.5, t_end=4.5,
    )
    net = make_net(topo.full(6, link_latency=1.0), engine="events",
                   partition=part)
    publish_on(net, 0, 1, 0.2)
    net.advance(4.0)                       # all deliveries inside the split
    assert (net.missing_rows() > 0).sum() == 3     # far side starved
    net.advance(5.0)                       # healed delivery at t=5
    assert net.synced()


# ---------------------------------------------------------------------------
# THE acceptance invariant: degenerate uniform delay == ticks, bitwise
# ---------------------------------------------------------------------------


IMPLS = ["fused", "scan"]


@pytest.mark.parametrize("impl", IMPLS)
def test_degenerate_limit_bitwise_equal_unit(impl):
    part = gossip_lib.PartitionSchedule(
        assignment=topo.split_halves(6), t_start=2.5, t_end=4.5,
    )
    top = topo.ring(6, link_latency=1.0, drop=0.3, seed=3)
    a = make_net(top, engine="ticks", partition=part, seed=7, impl=impl)
    b = make_net(top, engine="events", partition=part, seed=7, impl=impl)
    publish_on(a, 0, 1, 0.3)
    publish_on(b, 0, 1, 0.3)
    for t in (1.0, 2.0, 3.5, 6.0):
        a.advance(t)
        b.advance(t)
        if t == 2.0:
            publish_on(a, 2, 2, 2.1)
            publish_on(b, 2, 2, 2.1)
        assert_dags_equal(a.replicas.dags, b.replicas.dags, msg=f"t={t}:")
    # the PRNG streams stayed in lockstep (one split per tick == per batch),
    # so even a subsequent converge flush matches bitwise
    np.testing.assert_array_equal(np.asarray(a._key), np.asarray(b._key))
    assert a.converge(at_time=10.0) == b.converge(at_time=10.0)
    assert_dags_equal(a.replicas.dags, b.replicas.dags, msg="converge:")


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    overlay=st.sampled_from(["ring", "er", "star", "full"]),
    impl=st.sampled_from(IMPLS),
    drop=st.sampled_from([0.0, 0.3]),
    split=st.booleans(),
)
def test_property_degenerate_limit_bitwise(seed, overlay, impl, drop, split):
    """Property (acceptance): uniform per-edge delay == sync period makes
    the event engine's merge sequence bitwise the tick path over any
    overlay, loss rate, partition schedule, and publish interleaving."""
    n = 8
    builders = {
        "ring": lambda: topo.ring(n, link_latency=1.0, drop=drop,
                                  seed=seed % 997),
        "er": lambda: topo.erdos_renyi(n, 0.4, link_latency=1.0, drop=drop,
                                       seed=seed % 997),
        "star": lambda: topo.star(n, link_latency=1.0, drop=drop),
        "full": lambda: topo.full(n, link_latency=1.0, drop=drop),
    }
    part = (
        gossip_lib.PartitionSchedule(
            assignment=topo.split_halves(n), t_start=1.5, t_end=3.5,
        ) if split else None
    )
    top = builders[overlay]()
    a = make_net(top, engine="ticks", partition=part, seed=seed % 1013,
                 impl=impl)
    b = make_net(top, engine="events", partition=part, seed=seed % 1013,
                 impl=impl)
    rng = np.random.default_rng(seed)
    for seq in range(1, 4):
        node = int(rng.integers(0, n))
        publish_on(a, node, seq, 0.1 * seq)
        publish_on(b, node, seq, 0.1 * seq)
    for t in (1.0, 2.5, 5.0):
        a.advance(t)
        b.advance(t)
        assert_dags_equal(a.replicas.dags, b.replicas.dags, msg=f"t={t}:")
    np.testing.assert_array_equal(np.asarray(a._key), np.asarray(b._key))


def test_degenerate_overflow_window_fast_forwards_like_ticks():
    """An advance window longer than max_ticks_per_advance periods: the
    tick engine fast-forwards (elides the backlog AND its PRNG splits);
    the event engine must elide identically — same rounds, same key
    stream, same post-window schedule — or every later lossy round
    diverges permanently."""
    top = topo.ring(6, link_latency=1.0, drop=0.3, seed=3)
    a = make_net(top, engine="ticks", seed=7)
    b = make_net(top, engine="events", seed=7)
    publish_on(a, 0, 1, 0.3)
    publish_on(b, 0, 1, 0.3)
    a.advance(100.0)                  # 100 periods > the 64-tick cap
    b.advance(100.0)
    np.testing.assert_array_equal(np.asarray(a._key), np.asarray(b._key))
    assert_dags_equal(a.replicas.dags, b.replicas.dags, msg="overflow:")
    publish_on(a, 2, 2, 100.5)
    publish_on(b, 2, 2, 100.5)
    for t in (101.0, 104.0, 170.0):   # 170: a second overflowing window
        a.advance(t)
        b.advance(t)
        assert_dags_equal(a.replicas.dags, b.replicas.dags, msg=f"t={t}:")
    np.testing.assert_array_equal(np.asarray(a._key), np.asarray(b._key))


@pytest.mark.parametrize("impl", IMPLS)
def test_degenerate_bank_unlimited_bitwise(impl):
    """Bank gossip at unlimited capacity rides the degenerate limit too:
    rows AND transport state (have/credit/sent) bitwise the tick path."""
    top = topo.ring(6, link_latency=1.0, drop=0.2, seed=1)
    a = make_net(top, engine="ticks", impl=impl,
                 bank_cfg=BankGossipConfig(chunks_per_slot=4), seed=3)
    b = make_net(top, engine="events", impl=impl,
                 bank_cfg=BankGossipConfig(chunks_per_slot=4), seed=3)
    publish_on(a, 0, 1, 0.3)
    publish_on(b, 0, 1, 0.3)
    publish_on(a, 4, 2, 0.5)
    publish_on(b, 4, 2, 0.5)
    for t in (1.0, 3.0, 6.0):
        a.advance(t)
        b.advance(t)
        assert_dags_equal(a.replicas.dags, b.replicas.dags, msg=f"t={t}:")
        for f in ("have", "credit", "sent"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a.replicas.bank_state, f)),
                np.asarray(getattr(b.replicas.bank_state, f)),
                err_msg=f"t={t}:{f}",
            )


def test_e2e_degenerate_engines_bitwise():
    """run_dagfl_gossip: the full FL sim — Algorithm-2 prepare/commit
    interleaved through the same host driver — is bitwise identical across
    engines in the uniform-delay limit (curve, timing, union ledger)."""
    from repro.fl.experiments import default_dagfl_config, make_cnn_setup
    from repro.fl.systems import SimConfig, run_dagfl_gossip

    n = 8
    dcfg = default_dagfl_config(num_nodes=n)
    sim = SimConfig(iterations=10, eval_every=5, seed=0)
    results = []
    for engine in ("ticks", "events"):
        task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
        results.append(run_dagfl_gossip(
            task, nodes, dcfg, sim, gval,
            topology=topo.ring(n, link_latency=1.0, seed=0),
            gossip=gossip_lib.GossipConfig(sync_period=1.0, seed=0),
            engine=engine,
        ))
    base, ev = results
    np.testing.assert_array_equal(base.accs, ev.accs)
    np.testing.assert_array_equal(base.times, ev.times)
    assert_dags_equal(base.extras["dag"], ev.extras["dag"], msg="union:")
    assert ev.extras["events_processed"] > 0
    assert base.extras["events_processed"] == 0


# ---------------------------------------------------------------------------
# Bank chunk-drains: continuous accrual beats tick quantization
# ---------------------------------------------------------------------------


def test_bank_drains_recover_strided_bandwidth():
    """latency 2, period 1, 8 B/s links, 8 B chunks: the stride model fires
    every 2nd tick and forfeits the idle tick's budget (one chunk per 2 s);
    the event engine accrues continuously and drains a chunk every second —
    the payload completes in about half the time."""
    cfg = BankGossipConfig(chunks_per_slot=4)
    tick_net = make_net(topo.ring(2, link_latency=2.0, bandwidth=64.0),
                        engine="ticks", bank_cfg=cfg)
    ev_net = make_net(topo.ring(2, link_latency=2.0, bandwidth=64.0),
                      engine="events", bank_cfg=cfg)
    publish_on(tick_net, 0, 1, 0.2)
    publish_on(ev_net, 0, 1, 0.2)
    for t in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0):
        tick_net.advance(t)
        ev_net.advance(t)
    # events: row at t=2 (2 chunks accrued), drains at 3 and 4 -> done
    # ticks: one chunk per fired tick at t=1,3,5,7 -> done only at t=7
    assert int(ev_net.missing_chunks()[1]) == 0
    assert int(tick_net.missing_chunks()[1]) == 0
    ev2 = make_net(topo.ring(2, link_latency=2.0, bandwidth=64.0),
                   engine="events", bank_cfg=cfg)
    tick2 = make_net(topo.ring(2, link_latency=2.0, bandwidth=64.0),
                     engine="ticks", bank_cfg=cfg)
    publish_on(ev2, 0, 1, 0.2)
    publish_on(tick2, 0, 1, 0.2)
    ev2.advance(4.0)
    tick2.advance(4.0)
    assert int(ev2.missing_chunks()[1]) == 0        # strictly earlier
    assert int(tick2.missing_chunks()[1]) > 0


def test_bank_drain_respects_partition():
    """A partitioned link neither merges nor drains; after healing the
    payload completes without having banked the partition window."""
    part = gossip_lib.PartitionSchedule(
        assignment=np.asarray([0, 1]), t_start=0.5, t_end=6.5,
    )
    cfg = BankGossipConfig(chunks_per_slot=4)
    net = make_net(topo.ring(2, link_latency=1.0, bandwidth=64.0),
                   engine="events", bank_cfg=cfg, partition=part)
    publish_on(net, 0, 1, 0.2)
    net.advance(6.0)
    assert int(net.missing_rows()[1]) == 1          # row never crossed
    assert float(net.bytes_sent()) == 0.0
    net.advance(12.0)                               # healed: row + chunks
    assert int(net.missing_rows()[1]) == 0
    assert int(net.missing_chunks()[1]) == 0


# ---------------------------------------------------------------------------
# The §IV in-system simulation
# ---------------------------------------------------------------------------


def test_insystem_tips_match_eq4_on_bench_point():
    """Acceptance: the in-system tail-mean tip count lands within 15% of
    the Eq. (4) closed form on a well-connected overlay with delivery
    intervals well under h (bench-grid scale: benchmarks/stability_tips)."""
    cfg = DagFLConfig(num_nodes=16, alpha=5, k=2)
    f = 1.5e9
    pred = stability.equilibrium_tips(cfg, f)
    trace = events_lib.simulate_insystem_tips(
        topo.full(16), h=stability.iteration_delay(cfg, f),
        arrival_rate=cfg.arrival_rate, k=cfg.k, tau_max=cfg.tau_max,
        horizon=600.0, capacity=256, seed=0, sync_period=0.25,
    )
    assert trace.overflow == 0
    assert trace.published > 400                  # lambda=1 over 600 s
    sim = trace.tail_mean(0.5)
    assert sim == pytest.approx(pred, rel=0.15), (sim, pred)


def test_insystem_tips_scale_with_h():
    """Eq. (4): L0 is linear in h — quadrupling every node's iteration
    delay must raise the measured equilibrium accordingly."""
    top = topo.full(8)
    lo = events_lib.simulate_insystem_tips(
        top, h=1.0, arrival_rate=1.0, k=2, tau_max=60.0, horizon=250.0,
        capacity=256, seed=1, sync_period=0.25,
    )
    hi = events_lib.simulate_insystem_tips(
        top, h=4.0, arrival_rate=1.0, k=2, tau_max=60.0, horizon=250.0,
        capacity=256, seed=1, sync_period=0.25,
    )
    assert hi.tail_mean(0.5) > 1.8 * lo.tail_mean(0.5)


def test_insystem_slow_gossip_inflates_tips():
    """Stale views approve already-approved tips: a sluggish overlay floats
    the union tip count above the fast-gossip measurement."""
    cfg = dict(h=2.0, arrival_rate=1.0, k=2, tau_max=60.0, horizon=300.0,
               capacity=256, seed=0)
    fast = events_lib.simulate_insystem_tips(
        topo.full(8), sync_period=0.1, **cfg)
    slow = events_lib.simulate_insystem_tips(
        topo.ring(8, link_latency=4.0), sync_period=4.0, **cfg)
    assert slow.staleness.max() > fast.staleness.max()
    assert slow.tail_mean(0.5) > fast.tail_mean(0.5)


def test_insystem_trace_empty_tail_mean_is_nan():
    """The in-system trace shares stability.tail_mean's rule: an empty
    trace is NaN, never a silent 0.0 that reads as a zero-tip equilibrium."""
    tr = events_lib.InSystemTrace(
        times=np.zeros(0), tips=np.zeros(0), staleness=np.zeros(0),
        published=0, overflow=0, union=None,
    )
    assert np.isnan(tr.tail_mean())


def test_insystem_per_node_h_and_counters():
    """Heterogeneous h_i: every node still publishes (arrivals are uniform)
    and the union's per-node counters account every transaction."""
    h = np.asarray([0.5] * 6 + [6.0, 6.0], np.float32)   # two stragglers
    trace = events_lib.simulate_insystem_tips(
        topo.k_regular(8, 4), h=h, arrival_rate=1.0, k=2, tau_max=60.0,
        horizon=200.0, capacity=256, seed=2, sync_period=0.5,
    )
    pub = np.asarray(trace.union.published_per_node)
    assert trace.overflow == 0
    assert int(pub[:8].sum()) == trace.published
    assert (pub[:8] > 0).all()
