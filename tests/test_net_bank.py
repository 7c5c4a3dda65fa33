"""Bank gossip: content-addressed chunk transport over Table-I bandwidth.

Pins the three invariants of ``repro.net.bank``:

* the chunk-dedup reduction (Pallas kernel, interpreted here) is bitwise
  the pure-lax oracle, and transfer selection respects per-link whole-chunk
  budgets with rollover (property- and unit-tested);
* with UNLIMITED per-link capacity, ``run_dagfl_gossip`` with bank gossip
  enabled — and any ``GossipNetwork`` sync schedule, partitions included —
  is BITWISE the PR-3 bankless path for every round impl (the acceptance
  criterion: chunk transport is deterministic and never touches the PRNG
  stream);
* with finite capacity, availability lags row visibility at the configured
  bytes-per-tick rate, identical content dedups to zero bytes, and a
  partition/heal cycle reconverges availability, not just rows.
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
from repro.kernels import chunk_transfer as ck
from repro.kernels import ref as kernel_ref
from repro.net import bank as bank_lib
from repro.net import gossip as gossip_lib
from repro.net import replica as replica_lib
from repro.net import topology as topo
from repro.net.bank import BankGossipConfig

CAP, K = 16, 2
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Kernel layer: dedup reduction + transfer selection
# ---------------------------------------------------------------------------


def test_chunk_dedup_pallas_matches_ref_unit():
    rng = np.random.default_rng(0)
    dig = rng.integers(0, 5, (13, 3)).astype(np.float32)   # forced collisions
    have = rng.random((6, 13, 3)) < 0.3
    ref = np.asarray(kernel_ref.chunk_dedup_ref(jnp.asarray(have), jnp.asarray(dig)))
    out = np.asarray(ck.chunk_dedup_pallas(
        jnp.asarray(have), jnp.asarray(dig), block_s=4))
    np.testing.assert_array_equal(ref, out)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), s=st.integers(1, 20),
       c=st.integers(1, 4), vals=st.integers(2, 8))
def test_property_chunk_dedup_pallas_matches_ref(seed, s, c, vals):
    """Property: kernel == oracle on digest tables dense with collisions."""
    rng = np.random.default_rng(seed)
    dig = rng.integers(0, vals, (s, c)).astype(np.float32)
    have = rng.random((5, s, c)) < 0.4
    ref = np.asarray(kernel_ref.chunk_dedup_ref(jnp.asarray(have), jnp.asarray(dig)))
    out = np.asarray(ck.chunk_dedup_pallas(
        jnp.asarray(have), jnp.asarray(dig), block_s=8))
    np.testing.assert_array_equal(ref, out)


def test_chunk_dedup_same_content_across_slots():
    """A chunk held at ANY slot satisfies every same-digest chunk at that
    offset — the content-addressing that makes lazy republishes free."""
    dig = jnp.asarray([[1.0, 2.0], [1.0, 9.0], [7.0, 2.0]])
    have = jnp.zeros((1, 3, 2), bool).at[0, 0].set(True)   # only slot 0 held
    sat = np.asarray(ck.chunk_dedup(have, dig, impl="lax"))
    # slot 1 chunk 0 and slot 2 chunk 1 share slot 0's content
    np.testing.assert_array_equal(
        sat[0], [[True, True], [True, False], [False, True]]
    )


def test_transfer_select_budget_and_striping():
    need = jnp.asarray([[True, True, True]])
    src = jnp.asarray([[False, False, False],
                       [True, True, False],
                       [True, True, True]])
    edges = jnp.asarray([[False, True, True]])
    afford = jnp.asarray([[0, 1, 1]], jnp.int32)
    take, spent, pending = ck.transfer_select(need, src, edges, afford)
    # striping: chunk 0 (2 holders, 0 mod 2) -> sender 1; chunk 1 (1 mod 2)
    # -> sender 2; chunk 2 (sole holder) -> sender 2, over budget -> pending
    np.testing.assert_array_equal(np.asarray(take), [[True, True, False]])
    np.testing.assert_array_equal(np.asarray(spent), [[0, 1, 1]])
    np.testing.assert_array_equal(np.asarray(pending), [[False, False, True]])


def test_transfer_select_single_holder_is_lowest_index_rule():
    """One holder per chunk: striping degenerates to the PR-4 assignment."""
    need = jnp.asarray([[True, True]])
    src = jnp.asarray([[True, True], [False, False]])
    edges = jnp.asarray([[True, True]])
    afford = jnp.asarray([[2, 2]], jnp.int32)
    take, spent, pending = ck.transfer_select(need, src, edges, afford)
    np.testing.assert_array_equal(np.asarray(take), [[True, True]])
    np.testing.assert_array_equal(np.asarray(spent), [[2, 0]])
    np.testing.assert_array_equal(np.asarray(pending), [[False, False]])


def test_striping_uses_parallel_links_to_distinct_holders():
    """Satellite acceptance: two holders of the same content drain a slot
    in HALF the ticks — distinct chunks ride distinct links — where the
    PR-4 lowest-indexed assignment left the second link idle."""
    cfg = BankGossipConfig(chunks_per_slot=4)
    payload = jnp.arange(8.0)
    # slot 32 B over 4 chunks; 8 B/tick/link = one chunk per link per tick
    striped = make_net(topo.full(3, bandwidth=64.0), bank_cfg=cfg)
    publish_on(striped, 0, 1, 0.1, params=payload)
    publish_on(striped, 1, 2, 0.2, params=payload)   # identical content:
    # dedup makes BOTH 0 and 1 effective holders of every needed chunk
    control = make_net(topo.full(3, bandwidth=64.0), bank_cfg=cfg)
    publish_on(control, 0, 1, 0.1, params=payload)
    publish_on(control, 1, 2, 0.2, params=jnp.arange(8.0) + 100.0)  # distinct
    striped.advance(2.0)
    control.advance(2.0)
    # two holders, 4 distinct digests, 2 links x 1 chunk/tick -> 2 ticks
    assert int(striped.missing_chunks()[2]) == 0
    # single holder per slot: each slot needs 4 ticks on its own link
    assert int(control.missing_chunks()[2]) > 0
    # both of node 2's inbound links were actually paid for the same slot
    sent = np.asarray(striped.bank_state.sent)
    assert sent[2, 0] > 0 and sent[2, 1] > 0


def test_nan_payload_still_transfers_at_physical_identity():
    """Regression: a payload that trained to NaN digests to NaN, which
    compares unequal even to ITSELF — physical presence must short-circuit
    the digest match or the row would be gated out everywhere forever,
    committer included."""
    dig = jnp.asarray([[jnp.nan], [jnp.nan]])
    have = jnp.asarray([[[True], [False]]])       # node holds chunk (0, 0)
    for impl in ("lax", "pallas"):
        sat = np.asarray(ck.chunk_dedup(have, dig, impl=impl))
        assert sat[0, 0, 0], impl                 # physically held -> available
        assert not sat[0, 1, 0], impl             # NaN never dedups cross-slot
    # end to end: a NaN model still gossips and the run converges
    cfg = BankGossipConfig(chunks_per_slot=2)
    net = make_net(topo.ring(3, bandwidth=1e9), bank_cfg=cfg)
    publish_on(net, 0, 1, 0.2, params=jnp.full((8,), jnp.nan))
    assert net.converge(at_time=10.0)
    assert net.missing_chunks().max() == 0
    assert net.synced()


def test_chunk_digests_content_addressing():
    a = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((5,))}
    b = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.ones((5,))}
    c = {"w": jnp.arange(12.0).reshape(3, 4).at[1, 1].add(1e-3), "b": jnp.ones((5,))}
    da, db, dc = (np.asarray(bank_lib.chunk_digests(x, 4)) for x in (a, b, c))
    np.testing.assert_array_equal(da, db)          # identical content, same tags
    assert (da != dc).any()                        # a bit flip moves some tag


# ---------------------------------------------------------------------------
# GossipNetwork transport semantics
# ---------------------------------------------------------------------------


def genesis(num_nodes):
    d = dag_lib.empty_dag(CAP, K, num_nodes + 1)
    return dag_lib.publish(
        d, jnp.asarray(num_nodes, jnp.int32), jnp.float32(0.0),
        jnp.full((K,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(0, jnp.int32),
    )


def make_net(top, bank_cfg=None, sync_period=1.0, partition=None, seed=0,
             impl="fused"):
    return gossip_lib.GossipNetwork(
        genesis(top.num_nodes), bank=jnp.zeros((CAP, 8)), top=top,
        cfg=gossip_lib.GossipConfig(sync_period=sync_period, seed=seed, impl=impl),
        partition=partition, bank_cfg=bank_cfg,
    )


def publish_on(net, node, seq, t, params=None):
    d = net.read(node)
    d = replica_lib.publish_local(
        d, seq, jnp.asarray(node, jnp.int32), jnp.float32(t),
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


def test_finite_bandwidth_availability_lags_visibility():
    """slot = 32 B over 4 chunks; 8 B/s links move ONE chunk per tick, so a
    neighbor needs 4 ticks of payload for a row it saw after 1."""
    cfg = BankGossipConfig(chunks_per_slot=4)
    net = make_net(topo.ring(4, bandwidth=64.0), bank_cfg=cfg)   # 8 B/tick
    publish_on(net, 0, 1, 0.5)
    net.advance(1.0)
    assert int(net.missing_rows()[1]) == 0        # metadata arrived...
    assert int(net.missing_chunks()[1]) == 3      # ...3 of 4 chunks still owed
    for t in (2.0, 3.0, 4.0):
        net.advance(t)
    assert int(net.missing_chunks()[1]) == 0
    # the gated view hides the row until the payload completes
    net2 = make_net(topo.ring(4, bandwidth=64.0), bank_cfg=cfg)
    publish_on(net2, 0, 1, 0.5)
    net2.advance(1.0)
    assert int(net2.read(1).publisher[1]) == 0           # raw replica sees it
    assert int(net2.read_view(1).publisher[1]) == -1     # usable view does not
    assert int(net2.read_view(0).publisher[1]) == 0      # committer has chunks


def test_dedup_makes_identical_payload_free():
    """Same bytes at two slots: after the first slot's chunks arrive, the
    second costs zero transfer bytes (content addressing)."""
    cfg = BankGossipConfig(chunks_per_slot=4)
    payload = jnp.full((8,), 7.0)
    net = make_net(topo.ring(2, bandwidth=1e9), bank_cfg=cfg)
    publish_on(net, 0, 1, 0.2, params=payload)
    net.advance(1.0)
    bytes_first = net.bytes_sent()
    assert bytes_first > 0
    assert net.missing_chunks().max() == 0
    publish_on(net, 0, 2, 1.5, params=payload)    # identical content again
    net.advance(2.0)
    assert net.missing_chunks().max() == 0        # usable immediately...
    assert net.bytes_sent() == bytes_first        # ...and zero new bytes


def test_credit_rolls_over_for_subchunk_bandwidth():
    """A link slower than one chunk per tick banks partial progress: chunk
    bytes 8, capacity 3 B/tick -> the first chunk completes on the third
    tick the link fires (9 B accrued, 1 B residual kept)."""
    cfg = BankGossipConfig(chunks_per_slot=4)
    net = make_net(topo.ring(2, bandwidth=24.0), bank_cfg=cfg)   # 3 B/tick
    publish_on(net, 0, 1, 0.2)
    for t, expect in ((1.0, 4), (2.0, 4), (3.0, 3)):
        net.advance(t)   # the row is visible from tick 0; chunks trickle
        assert int(net.missing_chunks()[1]) == expect, t
    credit = np.asarray(net.bank_state.credit)
    assert 0.0 < credit[1, 0] < float(net._chunk_bytes)


def test_partition_blocks_chunks_then_heals():
    """Rows outrun payloads into a partition: metadata crosses before the
    split, in-flight chunks are stranded on the far side (credit pauses,
    not resets), and converge only drains them after healing — the
    bank-aware fixpoint predicate plus the drain-extended tick bound."""
    n = 4
    part = gossip_lib.PartitionSchedule(
        assignment=topo.split_halves(n), t_start=1.5, t_end=6.5,
    )
    # slot 32 B over 2 chunks; 8 B/tick -> 2 ticks per chunk, 4 per slot
    cfg = BankGossipConfig(chunks_per_slot=2)
    net = make_net(topo.full(n, bandwidth=64.0), bank_cfg=cfg, partition=part)
    publish_on(net, 0, 1, 0.2)
    net.advance(1.0)           # pre-split tick: row visible EVERYWHERE...
    assert int(net.missing_rows().max()) == 0
    assert (net.missing_chunks() > 0).sum() == 3   # ...payloads still owed
    net.advance(5.0)           # split: node 1 drains from 0; 2 and 3 starve
    missing = net.missing_chunks()
    assert missing[1] == 0 and missing[2] > 0 and missing[3] > 0
    assert not net.converge(at_time=5.0)      # still split: fixpoint != sync
    assert net.converge(at_time=7.0)          # healed: payloads drain
    assert net.missing_chunks().max() == 0
    assert net.synced()


def test_zero_bandwidth_never_delivers_payload():
    cfg = BankGossipConfig(chunks_per_slot=2)
    net = make_net(topo.ring(3, bandwidth=0.0), bank_cfg=cfg)
    publish_on(net, 0, 1, 0.2)
    net.advance(10.0)
    assert int(net.missing_rows().max()) == 0      # rows still travel free
    assert (net.missing_chunks() > 0).sum() == 2   # payload never will
    assert not net.converge(at_time=20.0)          # stall-detected, honest


# ---------------------------------------------------------------------------
# THE acceptance invariant: unlimited capacity == PR-3 path, bitwise
# ---------------------------------------------------------------------------


IMPLS = ["fused", "scan"]


@pytest.mark.parametrize("impl", IMPLS)
def test_infinite_bandwidth_schedule_bitwise_equal(impl):
    part = gossip_lib.PartitionSchedule(
        assignment=topo.split_halves(6), t_start=1.5, t_end=4.5,
    )
    a = make_net(topo.ring(6, drop=0.3, seed=3), partition=part, impl=impl)
    b = make_net(topo.ring(6, drop=0.3, seed=3), partition=part, impl=impl,
                 bank_cfg=BankGossipConfig(chunks_per_slot=4))
    for seq, node in ((1, 0), (2, 3), (3, 5)):
        publish_on(a, node, seq, 0.1 * seq)
        publish_on(b, node, seq, 0.1 * seq)
    for t in (1.0, 3.0, 6.0):
        a.advance(t)
        b.advance(t)
        assert_dags_equal(a.replicas.dags, b.replicas.dags, msg=f"t={t}:")
    assert a.converge(at_time=50.0) == b.converge(at_time=50.0)
    assert_dags_equal(a.replicas.dags, b.replicas.dags, msg="converge:")
    assert b.missing_chunks().max() == 0


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    overlay=st.sampled_from(["ring", "er", "star"]),
    impl=st.sampled_from(IMPLS),
    split=st.booleans(),
)
def test_property_infinite_bandwidth_bitwise(seed, overlay, impl, split):
    """Property (acceptance): any sync schedule over any overlay — losses,
    strides, partitions — leaves the dags trajectory bitwise unchanged by
    enabling bank gossip with unlimited capacity, and availability fully
    tracks visibility at every advance boundary."""
    n = 8
    builders = {
        "ring": lambda: topo.ring(n, drop=0.3, seed=seed % 997),
        "er": lambda: topo.erdos_renyi(n, 0.4, seed=seed % 997),
        "star": lambda: topo.star(n),
    }
    part = (
        gossip_lib.PartitionSchedule(
            assignment=topo.split_halves(n), t_start=1.0, t_end=3.0,
        ) if split else None
    )
    top = builders[overlay]()
    a = make_net(top, partition=part, seed=seed % 1013, impl=impl)
    b = make_net(top, partition=part, seed=seed % 1013, impl=impl,
                 bank_cfg=BankGossipConfig(chunks_per_slot=3))
    rng = np.random.default_rng(seed)
    for seq in range(1, 4):
        node = int(rng.integers(0, n))
        publish_on(a, node, seq, 0.1 * seq)
        publish_on(b, node, seq, 0.1 * seq)
    for t in (2.0, 5.0):
        a.advance(t)
        b.advance(t)
        assert_dags_equal(a.replicas.dags, b.replicas.dags, msg=f"t={t}:")
        # payload availability == row visibility in the infinite-bw limit
        assert b.missing_chunks().max() == 0
    assert a.converge(at_time=20.0) == b.converge(at_time=20.0)
    assert_dags_equal(a.replicas.dags, b.replicas.dags, msg="converge:")


@pytest.mark.parametrize("impl", IMPLS)
def test_e2e_infinite_bandwidth_sim_bitwise(impl):
    """run_dagfl_gossip: bank gossip with unlimited capacity reproduces the
    PR-3 run exactly — curve, union ledger, and timing."""
    from repro.fl.experiments import default_dagfl_config, make_cnn_setup
    from repro.fl.systems import SimConfig, run_dagfl_gossip

    n = 8
    dcfg = default_dagfl_config(num_nodes=n)
    sim = SimConfig(iterations=10, eval_every=5, seed=0)
    results = []
    for bg in (None, BankGossipConfig(chunks_per_slot=4)):
        task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
        results.append(run_dagfl_gossip(
            task, nodes, dcfg, sim, gval,
            topology=topo.ring(n, seed=0),
            gossip=gossip_lib.GossipConfig(sync_period=1.0, seed=0, impl=impl),
            bank_gossip=bg,
        ))
    base, banked = results
    np.testing.assert_array_equal(base.accs, banked.accs)
    np.testing.assert_array_equal(base.times, banked.times)
    assert_dags_equal(base.extras["dag"], banked.extras["dag"], msg="union:")
    assert base.extras["sync_rounds"] == banked.extras["sync_rounds"]
    assert banked.extras["bank_missing_final"].max() == 0
    assert banked.extras["bank_bytes_sent"] > 0     # transport was accounted


def test_e2e_table1_bandwidth_runs_and_reports_lag():
    """Table-I priced links at bench scale: the sim stays finite and the
    transport metrics expose the payload lag and the byte bill."""
    from repro.fl.experiments import default_dagfl_config, make_cnn_setup
    from repro.fl.systems import SimConfig, run_dagfl_gossip

    n = 8
    dcfg = default_dagfl_config(num_nodes=n)
    sim = SimConfig(iterations=10, eval_every=5, seed=0)
    task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
    res = run_dagfl_gossip(
        task, nodes, dcfg, sim, gval,
        topology=topo.ring(n, seed=0, bandwidth=1e4),   # starved uplink
        gossip=gossip_lib.GossipConfig(sync_period=1.0, seed=0),
        bank_gossip=BankGossipConfig(chunks_per_slot=4, slot_bytes=7e6),
    )
    assert np.all(np.isfinite(res.accs))
    assert res.extras["bank_lag_curve"].shape[1] == 3
    assert res.extras["bank_missing_final"].max() > 0   # payload really lags
    assert res.extras["bank_bytes_sent"] >= 0


def test_bank_mesh_equivalence_in_subprocess():
    """Runs on every lane: forces 8 host devices in a child process and
    checks a finite-bandwidth bank-gossip schedule bitwise against the
    single-device network (the sharded tick all-gathers availability
    bitmaps, never payloads)."""
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import dag as dag_lib
        from repro.net import gossip as G, mesh as M, replica as R
        from repro.net import topology as topo
        from repro.net.bank import BankGossipConfig
        assert jax.device_count() == 8, jax.device_count()
        CAP, K = 16, 2
        d = dag_lib.empty_dag(CAP, K, 17)
        d = dag_lib.publish(d, jnp.asarray(16, jnp.int32), jnp.float32(0.0),
            jnp.full((K,), dag_lib.NO_TX, jnp.int32), jnp.float32(0.5),
            jnp.float32(0.0), jnp.asarray(0, jnp.int32))
        def net(mesh):
            return G.GossipNetwork(d, bank=jnp.zeros((CAP, 8)),
                top=topo.ring(16, drop=0.2, seed=1, bandwidth=96.0),
                cfg=G.GossipConfig(sync_period=1.0, seed=5),
                bank_cfg=BankGossipConfig(chunks_per_slot=4), mesh=mesh)
        a, b = net(None), net(M.make_gossip_mesh(nodes=2, model=4))
        for n_ in (a, b):
            dd = R.publish_local(n_.read(3), 1, jnp.asarray(3, jnp.int32),
                jnp.float32(0.1), jnp.full((K,), dag_lib.NO_TX, jnp.int32),
                jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(1, jnp.int32))
            n_.write(3, dd)
            n_.bank_commit(3, 1, jnp.full((8,), 2.0))
        a.advance(5.0); b.advance(5.0)
        assert a.converge(at_time=60.0) == b.converge(at_time=60.0)
        for f in dag_lib.DagState._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a.replicas.dags, f)),
                np.asarray(getattr(b.replicas.dags, f)), err_msg=f)
        for f in ("have", "credit", "sent"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a.replicas.bank_state, f)),
                np.asarray(getattr(b.replicas.bank_state, f)), err_msg=f)
        np.testing.assert_array_equal(a.missing_chunks(), b.missing_chunks())
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        timeout=1200,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "OK" in out.stdout
