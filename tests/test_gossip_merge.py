"""Fused anti-entropy fast path: bitwise equivalence with the PR-1 fold.

The fused round (winner reduction + payload gather, ``repro.kernels.
gossip_merge`` + ``dag.merge_select``) must be BITWISE-identical to the
reference ``vmap``-over-``scan`` fold of ``dag.merge`` — on adversarial
random states (duplicate keys with divergent payloads, empty rows, random
masks), not just states reachable through ``publish``. Likewise one
tick-batched ``advance`` must equal the same ticks issued one dispatch at a
time, and the ``lax.while_loop`` ``converge`` must behave like the host
loop it replaced.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import dag as dag_lib
from repro.kernels import ref as kref
from repro.kernels.gossip_merge import gossip_winner, gossip_winner_pallas
from repro.net import gossip as gossip_lib
from repro.net import replica as replica_lib
from repro.net import topology as topo

CAP, K = 16, 2
IMPLS = ["fused", "lax", "pallas"]


def random_stacked(rng, r, cap=CAP, num_nodes=8, k=K) -> dag_lib.DagState:
    """Random stacked replicas — intentionally NOT publish-reachable: the
    same (publish_time, publisher) key can carry different payloads on
    different replicas, so the tests pin the tie-break order, not just the
    CRDT happy path."""
    pub = rng.integers(-1, num_nodes, (r, cap)).astype(np.int32)
    t = np.where(pub >= 0, rng.integers(0, 4, (r, cap)) * 0.5, 0.0)
    approvers = (rng.random((r, cap, num_nodes)) < 0.3) & (pub[..., None] >= 0)
    return dag_lib.DagState(
        publisher=jnp.asarray(pub),
        publish_time=jnp.asarray(t, jnp.float32),
        approvals=jnp.asarray(rng.integers(-1, cap, (r, cap, k)), jnp.int32),
        approvers=jnp.asarray(approvers),
        approval_count=jnp.asarray(approvers.sum(-1), jnp.int32),
        accuracy=jnp.asarray(rng.random((r, cap)), jnp.float32),
        auth_tag=jnp.asarray(rng.random((r, cap)), jnp.float32),
        model_slot=jnp.asarray(rng.integers(-1, cap, (r, cap)), jnp.int32),
        count=jnp.asarray(rng.integers(0, 3 * cap, (r,)), jnp.int32),
        published_per_node=jnp.asarray(rng.integers(0, 5, (r, num_nodes)), jnp.int32),
        contributing_m0=jnp.asarray(rng.integers(0, 5, (r, num_nodes)), jnp.int32),
        contributing_m1=jnp.asarray(rng.integers(0, 5, (r, num_nodes)), jnp.int32),
    )


def assert_dags_equal(a: dag_lib.DagState, b: dag_lib.DagState) -> None:
    for name in dag_lib.DagState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)), err_msg=name
        )


def _edge_cases(r):
    return [
        np.zeros((r, r), bool),                    # nobody hears anybody
        np.ones((r, r), bool) & ~np.eye(r, dtype=bool),  # full overlay
        np.triu(np.ones((r, r), bool), 1),         # asymmetric delivery
    ]


@pytest.mark.parametrize("impl", IMPLS)
def test_fused_round_matches_scan_on_random_states(impl):
    rng = np.random.default_rng(0)
    scan = gossip_lib.make_gossip_round("scan")
    fused = gossip_lib.make_gossip_round(impl)
    r = 9
    masks = _edge_cases(r) + [rng.random((r, r)) < 0.4 for _ in range(6)]
    for edges in masks:
        dags = random_stacked(rng, r)
        assert_dags_equal(scan(dags, jnp.asarray(edges)), fused(dags, jnp.asarray(edges)))


def test_pallas_kernel_matches_lax_oracle_all_block_widths():
    """The Pallas kernel (interpret mode here) against the pure-lax oracle,
    for every tile-legal block width: one full-width block, and 128- /
    256-row blocks that force column padding; plus a width the TPU tiling
    rule refuses."""
    rng = np.random.default_rng(1)
    cap = 300
    for bc in (128, 256, 512):         # 512 > cap: single full-width block
        dags = random_stacked(rng, 7, cap=cap)
        mask = jnp.asarray(rng.random((7, 7)) < 0.5) | jnp.eye(7, dtype=bool)
        ref_out = kref.gossip_winner_ref(
            dags.publish_time, dags.publisher, dags.approval_count, mask
        )
        pal_out = gossip_winner_pallas(
            dags.publish_time, dags.publisher, dags.approval_count, mask,
            block_c=bc, interpret=True,
        )
        for a, b in zip(ref_out, pal_out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="multiple of 128"):
        gossip_winner_pallas(
            dags.publish_time, dags.publisher, dags.approval_count, mask,
            block_c=64, interpret=True,
        )


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    r=st.integers(2, 12),
    cap=st.integers(1, 24),
    edge_p=st.floats(0.0, 1.0),
)
def test_property_fused_round_equals_scan(seed, r, cap, edge_p):
    rng = np.random.default_rng(seed)
    dags = random_stacked(rng, r, cap=cap)
    edges = jnp.asarray(rng.random((r, r)) < edge_p)
    scan = gossip_lib.make_gossip_round("scan")(dags, edges)
    for impl in IMPLS:
        assert_dags_equal(scan, gossip_lib.make_gossip_round(impl)(dags, edges))


def test_merge_all_matches_sequential_fold():
    """The union reduction (Rr=1 winner pass) == left fold of dag.merge."""
    rng = np.random.default_rng(2)
    for _ in range(5):
        dags = random_stacked(rng, 6)
        replicas = [
            jax.tree_util.tree_map(lambda x: x[i], dags) for i in range(6)
        ]
        folded = functools.reduce(dag_lib.merge, replicas)
        assert_dags_equal(folded, replica_lib.merge_all(dags))


# ---------------------------------------------------------------------------
# Tick batching / device-resident converge
# ---------------------------------------------------------------------------


def _genesis(num_nodes):
    d = dag_lib.empty_dag(CAP, K, num_nodes + 1)
    return dag_lib.publish(
        d, jnp.asarray(num_nodes, jnp.int32), jnp.float32(0.0),
        jnp.full((K,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(0, jnp.int32),
    )


def _make_net(top, impl, sync_period=1.0, partition=None, seed=0):
    n = top.num_nodes
    return gossip_lib.GossipNetwork(
        _genesis(n), bank=jnp.zeros((CAP, 4)), top=top,
        cfg=gossip_lib.GossipConfig(sync_period=sync_period, seed=seed, impl=impl),
        partition=partition,
    )


def _seed_rows(net, rng, count=5):
    for seq in range(1, count + 1):
        node = int(rng.integers(0, net.topology.num_nodes))
        d = net.read(node)
        d = replica_lib.publish_local(
            d, seq, jnp.asarray(node, jnp.int32), jnp.float32(0.1 * seq),
            jnp.full((K,), dag_lib.NO_TX, jnp.int32),
            jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(seq % CAP, jnp.int32),
        )
        net.write(node, d)


@pytest.mark.parametrize("impl", ["fused", "scan"])
def test_batched_advance_equals_sequential_ticks(impl):
    """advance(t) over a k-tick window == k _tick_once calls, bitwise —
    including PRNG-driven message loss and latency strides — in ONE
    device dispatch."""
    top = topo.ring(8, link_latency=2.0, drop=0.3, seed=3)
    batched = _make_net(top, impl, seed=7)
    stepped = _make_net(top, impl, seed=7)
    rng = np.random.default_rng(4)
    _seed_rows(batched, rng)
    _seed_rows(stepped, np.random.default_rng(4))

    calls_before = batched.device_calls
    batched.advance(8.0)                    # 8 periods -> one 8-tick batch
    assert batched.device_calls == calls_before + 1

    while stepped._next_tick_t <= 8.0:
        stepped._tick_once(stepped._next_tick_t)
        stepped._next_tick_t += stepped.cfg.sync_period

    assert batched.tick == stepped.tick == 8
    assert batched.rounds_run == stepped.rounds_run == 8
    assert_dags_equal(batched.replicas.dags, stepped.replicas.dags)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), window=st.integers(1, 12))
def test_property_batched_advance_equals_sequential(seed, window):
    top = topo.k_regular(8, 4, drop=0.2, seed=seed % 997)
    batched = _make_net(top, "fused", seed=seed % 1013)
    stepped = _make_net(top, "fused", seed=seed % 1013)
    rng = np.random.default_rng(seed)
    _seed_rows(batched, rng, count=3)
    _seed_rows(stepped, np.random.default_rng(seed), count=3)
    batched.advance(float(window))
    while stepped._next_tick_t <= float(window):
        stepped._tick_once(stepped._next_tick_t)
        stepped._next_tick_t += stepped.cfg.sync_period
    assert_dags_equal(batched.replicas.dags, stepped.replicas.dags)


@pytest.mark.parametrize("impl", ["fused", "scan"])
def test_converge_is_single_dispatch_and_reaches_fixpoint(impl):
    net = _make_net(topo.ring(8, link_latency=3.0), impl)
    _seed_rows(net, np.random.default_rng(5))
    calls = net.device_calls
    assert net.converge(at_time=100.0)
    assert net.device_calls == calls + 1    # whole fixpoint loop on device
    assert net.synced()
    # tick/rounds bookkeeping advanced together with the on-device loop
    assert net.tick == net.rounds_run > 0


def test_converge_respects_active_partition():
    n = 8
    part = gossip_lib.PartitionSchedule(
        assignment=topo.split_halves(n), t_start=0.0, t_end=100.0
    )
    net = _make_net(topo.full(n), "fused", partition=part)
    _seed_rows(net, np.random.default_rng(6))
    assert not net.converge(at_time=50.0)      # split: fixpoint != full sync
    assert net.converge(at_time=200.0)         # healed: full sync
