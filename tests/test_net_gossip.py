"""Anti-entropy gossip: propagation, loss, latency strides, partition/heal.

Propagation-semantics tests run under both round implementations —
``impl="scan"`` (the PR-1 reference fold) and ``impl="fused"`` (the kernel
reduction fast path) — they must be indistinguishable.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dag as dag_lib
from repro.net import gossip as gossip_lib
from repro.net import replica as replica_lib
from repro.net import topology as topo

CAP, K = 32, 2


def genesis(num_nodes):
    d = dag_lib.empty_dag(CAP, K, num_nodes + 1)
    return dag_lib.publish(
        d, jnp.asarray(num_nodes, jnp.int32), jnp.float32(0.0),
        jnp.full((K,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(0, jnp.int32),
    )


IMPLS = ["fused", "scan"]


def make_net(top, sync_period=1.0, partition=None, seed=0, impl="fused"):
    n = top.num_nodes
    return gossip_lib.GossipNetwork(
        genesis(n), bank=jnp.zeros((CAP, 4)), top=top,
        cfg=gossip_lib.GossipConfig(sync_period=sync_period, seed=seed, impl=impl),
        partition=partition,
    )


def publish_on(net, node, seq, t, approvals=None):
    ap = approvals if approvals is not None else jnp.full((K,), dag_lib.NO_TX, jnp.int32)
    d = net.read(node)
    d = replica_lib.publish_local(
        d, seq, jnp.asarray(node, jnp.int32), jnp.float32(t), ap,
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(seq % CAP, jnp.int32),
    )
    net.write(node, d)


def test_replica_roundtrip_and_shared_start():
    net = make_net(topo.ring(5))
    assert net.replicas.num_replicas == 5
    assert net.synced()
    d0 = net.read(3)
    assert int(d0.count) == 1
    publish_on(net, 3, seq=1, t=0.5)
    assert not net.synced()
    assert int(net.read(3).count) == 2
    assert int(net.read(0).count) == 1          # others unaffected until sync


@pytest.mark.parametrize("pick", ["first", "second", "last"])
def test_compiled_read_matches_eager_slices(pick):
    """The compiled gather returns, bitwise for every leaf, what slicing
    each stacked leaf eagerly returns, on replicas whose rows differ."""
    r = 6
    net = make_net(topo.ring(r))
    publish_on(net, 0, seq=1, t=0.5)
    publish_on(net, 1, seq=2, t=0.7, approvals=jnp.asarray([0, dag_lib.NO_TX], jnp.int32))
    publish_on(net, r - 1, seq=3, t=0.9, approvals=jnp.asarray([0, 1], jnp.int32))
    assert not net.synced()
    i = {"first": 0, "second": 1, "last": r - 1}[pick]
    got = net.read(i)
    want = jax.tree_util.tree_map(lambda x: x[i], net.replicas.dags)
    for name in dag_lib.DagState._fields:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_reading_every_node_traces_the_gather_once():
    """Node ids are traced arguments: 16 ids, Python or numpy, share one
    program (a capacity no other test uses makes the cache delta exact)."""
    n = 16
    d = dag_lib.publish(
        dag_lib.empty_dag(24, 3, n + 1), jnp.asarray(n, jnp.int32),
        jnp.float32(0.0), jnp.full((3,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(0, jnp.int32),
    )
    net = gossip_lib.GossipNetwork(
        d, bank=jnp.zeros((24, 4)), top=topo.ring(n),
        cfg=gossip_lib.GossipConfig(sync_period=1.0, seed=0),
    )
    before = replica_lib._read_dags._cache_size()
    for i in range(n):
        net.read(i if i % 2 else np.int64(i))
    assert replica_lib._read_dags._cache_size() - before == 1
    assert net.read_calls == n
    assert net.device_calls == 0        # a read advances no state


@pytest.mark.parametrize("impl", IMPLS)
def test_ring_propagates_one_hop_per_tick(impl):
    net = make_net(topo.ring(6), impl=impl)
    publish_on(net, 0, seq=1, t=0.5)
    assert (net.missing_rows() > 0).sum() == 5
    net.advance(1.0)                             # neighbors 1 and 5 learn
    assert (net.missing_rows() > 0).sum() == 3
    net.advance(2.0)
    assert (net.missing_rows() > 0).sum() == 1
    net.advance(3.0)                             # antipode reached
    assert net.synced()


@pytest.mark.parametrize("impl", IMPLS)
def test_full_drop_blocks_everything(impl):
    net = make_net(topo.ring(6, drop=1.0), impl=impl)
    publish_on(net, 0, seq=1, t=0.5)
    net.advance(10.0)
    assert (net.missing_rows() > 0).sum() == 5
    assert not net.synced()


@pytest.mark.parametrize("impl", IMPLS)
def test_latency_stride_halves_sync_rate(impl):
    # link latency 2x the period: links fire only on even ticks
    net = make_net(topo.ring(6, link_latency=2.0), sync_period=1.0, impl=impl)
    publish_on(net, 0, seq=1, t=0.1)
    net.advance(1.0)                             # tick 0 fires (0 % 2 == 0)
    assert (net.missing_rows() > 0).sum() == 3
    net.advance(2.0)                             # tick 1: strided out, no-op
    assert (net.missing_rows() > 0).sum() == 3
    net.advance(3.0)                             # tick 2 fires
    assert (net.missing_rows() > 0).sum() == 1


@pytest.mark.parametrize("impl", IMPLS)
def test_gossip_round_is_single_jitted_call(impl):
    """The round must accept the whole stacked replica set in one call."""
    net = make_net(topo.full(8), impl=impl)
    publish_on(net, 2, seq=1, t=0.5)
    round_fn = gossip_lib.make_gossip_round(impl)
    edges = jnp.asarray(net.topology.adjacency)
    out = round_fn(net.replicas.dags, edges)     # (R, ...) in, (R, ...) out
    assert out.publisher.shape == net.replicas.dags.publisher.shape
    assert bool(replica_lib.replicas_synced(out))


def test_union_view_counts():
    net = make_net(topo.ring(4))
    publish_on(net, 0, seq=1, t=0.5)
    publish_on(net, 2, seq=2, t=0.6, approvals=jnp.asarray([0, dag_lib.NO_TX], jnp.int32))
    union = net.union()
    assert int(union.count) == 3
    assert int(jnp.sum(union.publisher >= 0)) == 3
    assert int(union.approval_count[0]) == 1     # node 2's credit survives union


@pytest.mark.parametrize("impl", IMPLS)
def test_partition_then_heal_converges_identically(impl):
    """Acceptance: split for [t_a, t_b), publish on both sides, heal -> all
    replicas converge to the identical DagState."""
    n = 8
    part = gossip_lib.PartitionSchedule(
        assignment=topo.split_halves(n), t_start=1.5, t_end=6.5,
    )
    net = make_net(topo.full(n), sync_period=1.0, partition=part, impl=impl)

    publish_on(net, 0, seq=1, t=0.2)             # pre-partition: reaches all
    net.advance(1.0)
    assert net.synced()

    # during the partition each side publishes its own history
    publish_on(net, 1, seq=2, t=2.0, approvals=jnp.asarray([1, -1], jnp.int32))
    publish_on(net, 5, seq=3, t=2.1, approvals=jnp.asarray([1, -1], jnp.int32))
    net.advance(3.0)                             # intra-component sync only
    left, right = net.read(0), net.read(n - 1)
    assert int(left.count) == 3                  # side A saw seq 2
    assert int(right.count) == 4                 # side B saw seq 3
    assert not net.synced()
    # row 2 is visible on side A, row 3 on side B — disjoint views
    assert int(left.publisher[3]) < 0 and int(right.publisher[3]) >= 0
    assert int(left.publisher[2]) >= 0 and int(right.publisher[2]) < 0

    net.advance(7.0)                             # schedule healed at t=6.5
    assert net.converge(at_time=8.0)
    assert net.synced()
    merged = net.read(0)
    union = net.union()
    for a, b in zip(jax.tree_util.tree_leaves(merged), jax.tree_util.tree_leaves(union)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # both divergent rows survive, and the shared ancestor's concurrent
    # credits from either side of the partition BOTH count after healing
    # (the exact approver-set union; union-by-max used to collapse them)
    assert int(union.publisher[2]) == 1 and int(union.publisher[3]) == 5
    assert int(union.approval_count[1]) == 2
    assert bool(union.approvers[1, 1]) and bool(union.approvers[1, 5])


@pytest.mark.parametrize("impl", IMPLS)
def test_ideal_wire_ignores_link_latency(impl):
    """sync_period <= 0 is an ideal wire: latency strides must not apply
    (regression: ceil(latency/1e-9) overflowed int32 and disabled gossip)."""
    net = make_net(topo.ring(6, link_latency=2.5), sync_period=0.0, impl=impl)
    publish_on(net, 0, seq=1, t=0.5)
    net.advance(1.0)
    assert net.synced()


@pytest.mark.parametrize("impl", IMPLS)
def test_converge_covers_strided_links(impl):
    """converge()'s tick bound must account for links that only fire every
    ceil(latency/period) ticks (regression: bound was num_nodes alone)."""
    net = make_net(topo.ring(8, link_latency=3.0), sync_period=1.0, impl=impl)
    publish_on(net, 0, seq=1, t=0.1)
    assert net.converge(at_time=100.0)
    assert net.synced()


@pytest.mark.parametrize("impl", IMPLS)
def test_disconnected_overlay_never_converges(impl):
    net = make_net(topo.erdos_renyi(6, 0.0), impl=impl)     # no links at all
    publish_on(net, 0, seq=1, t=0.1)
    assert not net.converge(at_time=5.0)
