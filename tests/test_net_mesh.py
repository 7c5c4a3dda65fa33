"""Mesh-sharded gossip == single-device gossip, bitwise.

The sharded round (``repro.net.mesh`` + ``gossip._shard_round``) partitions
the ReplicaSet's leading receiver axis over the mesh's "nodes" axis: each
shard all-gathers the sender rows once, winner-reduces its own receiver
block, and writes back only that block. Everything here asserts BITWISE
equality with the single-device paths — the one-shot round (all impls), the
tick-batched ``advance`` scan, and the while-loop ``converge``, including a
partition/heal schedule — on ring / Erdős–Rényi / star overlays.

Multi-device tests need ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the CI 8-device lane) and skip on single-device runners; one subprocess
test pins those flags itself so every lane exercises the mesh path.
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
from repro.net import gossip as gossip_lib
from repro.net import mesh as mesh_lib
from repro.net import replica as replica_lib
from repro.net import topology as topo

CAP, K = 16, 2
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

multidevice = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
    "(the CI 8-device lane)",
)


def random_stacked(rng, r, cap=CAP, num_nodes=8, k=K) -> dag_lib.DagState:
    """Adversarial random replicas (same generator as test_gossip_merge):
    duplicate keys with divergent payloads pin the tie-break order, not just
    the CRDT happy path."""
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


def assert_dags_equal(a: dag_lib.DagState, b: dag_lib.DagState, msg="") -> None:
    for name in dag_lib.DagState._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{msg}{name}",
        )


def genesis(num_nodes):
    d = dag_lib.empty_dag(CAP, K, num_nodes + 1)
    return dag_lib.publish(
        d, jnp.asarray(num_nodes, jnp.int32), jnp.float32(0.0),
        jnp.full((K,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(0, jnp.int32),
    )


def make_net(top, mesh=None, impl="fused", sync_period=1.0, partition=None, seed=0):
    return gossip_lib.GossipNetwork(
        genesis(top.num_nodes), bank=jnp.zeros((CAP, 4)), top=top,
        cfg=gossip_lib.GossipConfig(sync_period=sync_period, seed=seed, impl=impl),
        partition=partition, mesh=mesh,
    )


def publish_on(net, node, seq, t):
    d = net.read(node)
    d = replica_lib.publish_local(
        d, seq, jnp.asarray(node, jnp.int32), jnp.float32(t),
        jnp.full((K,), dag_lib.NO_TX, jnp.int32),
        jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(seq % CAP, jnp.int32),
    )
    net.write(node, d)


OVERLAYS = {
    "ring": lambda n, seed: topo.ring(n, drop=0.3, seed=seed),
    "er": lambda n, seed: topo.erdos_renyi(n, 0.3, seed=seed),
    "star": lambda n, seed: topo.star(n),
}


# ---------------------------------------------------------------------------
# Mesh construction / validation (device-count independent)
# ---------------------------------------------------------------------------


def test_mesh_single_node_axis_accepts_any_overlay():
    mesh = mesh_lib.make_gossip_mesh(nodes=1)
    assert mesh_lib.nodes_axis_size(mesh) == 1
    mesh_lib.validate_replica_mesh(7, mesh)   # nodes=1 divides everything
    # a single-shard mesh still runs the shard_map path end to end
    net = make_net(topo.ring(6), mesh=mesh)
    publish_on(net, 0, 1, 0.5)
    assert net.converge(at_time=50.0)
    ref = make_net(topo.ring(6))
    publish_on(ref, 0, 1, 0.5)
    assert ref.converge(at_time=50.0)
    assert_dags_equal(net.replicas.dags, ref.replicas.dags, msg="1-shard:")


def test_mesh_needs_enough_devices():
    with pytest.raises(ValueError):
        mesh_lib.make_gossip_mesh(nodes=jax.device_count() + 1)


@multidevice
def test_mesh_rejects_indivisible_overlay():
    mesh = mesh_lib.make_gossip_mesh(nodes=8)
    with pytest.raises(ValueError):
        mesh_lib.validate_replica_mesh(7, mesh)
    with pytest.raises(ValueError):
        make_net(topo.ring(9), mesh=mesh)


# ---------------------------------------------------------------------------
# One-shot round equivalence (all impls, 2x4 and 8x1 meshes)
# ---------------------------------------------------------------------------


@multidevice
@pytest.mark.parametrize("mesh_shape", [(2, 4), (8, 1)])
@pytest.mark.parametrize("impl", ["fused", "lax", "pallas", "scan"])
def test_sharded_round_matches_single_device(mesh_shape, impl):
    mesh = mesh_lib.make_gossip_mesh(nodes=mesh_shape[0], model=mesh_shape[1])
    rng = np.random.default_rng(0)
    r = 16
    single = gossip_lib.make_gossip_round(impl)
    sharded = gossip_lib.make_gossip_round(impl, mesh=mesh)
    for edges in [np.zeros((r, r), bool), np.triu(np.ones((r, r), bool), 1)] + [
        rng.random((r, r)) < 0.4 for _ in range(3)
    ]:
        dags = random_stacked(rng, r)
        assert_dags_equal(
            single(dags, jnp.asarray(edges)), sharded(dags, jnp.asarray(edges)),
            msg=f"{mesh_shape}/{impl}/",
        )


# ---------------------------------------------------------------------------
# Driver equivalence: advance windows, converge, partition/heal
# ---------------------------------------------------------------------------


@multidevice
@pytest.mark.parametrize("overlay", sorted(OVERLAYS))
def test_mesh_network_advance_and_heal_bitwise(overlay):
    n = 16
    mesh = mesh_lib.make_gossip_mesh(nodes=8)
    part = gossip_lib.PartitionSchedule(
        assignment=topo.split_halves(n), t_start=2.5, t_end=6.5
    )
    a = make_net(OVERLAYS[overlay](n, 3), partition=part, seed=7)
    b = make_net(OVERLAYS[overlay](n, 3), mesh=mesh, partition=part, seed=7)
    rng = np.random.default_rng(4)
    for seq in range(1, 5):
        node = int(rng.integers(0, n))
        publish_on(a, node, seq, 0.1 * seq)
        publish_on(b, node, seq, 0.1 * seq)
    for t in (1.0, 3.0, 5.0, 8.0):      # pre-partition, split, split, healed
        a.advance(t)
        b.advance(t)
        assert_dags_equal(a.replicas.dags, b.replicas.dags, msg=f"{overlay}@{t}:")
    sa, sb = a.converge(at_time=100.0), b.converge(at_time=100.0)
    assert sa == sb
    assert_dags_equal(a.replicas.dags, b.replicas.dags, msg=f"{overlay}@conv:")
    assert b.synced() == a.synced()


@multidevice
def test_mesh_replicas_actually_sharded():
    """The point of the exercise: each device holds R/shards receiver rows."""
    n, shards = 16, 8
    net = make_net(topo.ring(n), mesh=mesh_lib.make_gossip_mesh(nodes=shards))
    pub = net.replicas.dags.publisher
    assert len(pub.sharding.device_set) == shards
    shard_rows = {s.data.shape[0] for s in pub.addressable_shards}
    assert shard_rows == {n // shards}
    net.advance(2.0)                     # sharding survives the jitted scan
    assert len(net.replicas.dags.publisher.sharding.device_set) == shards


@multidevice
@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    overlay=st.sampled_from(sorted(OVERLAYS)),
    window=st.integers(1, 8),
    split=st.booleans(),
)
def test_property_mesh_round_equals_fused(seed, overlay, window, split):
    """Property: a mesh-sharded sync schedule — optionally through a
    partition/heal — is bitwise the single-device fused schedule (and hence,
    by test_gossip_merge, the PR-1 scan fold)."""
    n = 16
    mesh = mesh_lib.make_gossip_mesh(nodes=8)
    part = (
        gossip_lib.PartitionSchedule(
            assignment=topo.split_halves(n),
            t_start=1.0, t_end=1.0 + window / 2.0,
        )
        if split else None
    )
    top = OVERLAYS[overlay](n, seed % 997)
    a = make_net(top, partition=part, seed=seed % 1013)
    b = make_net(top, mesh=mesh, partition=part, seed=seed % 1013)
    rng = np.random.default_rng(seed)
    for seq in range(1, 4):
        node = int(rng.integers(0, n))
        publish_on(a, node, seq, 0.1 * seq)
        publish_on(b, node, seq, 0.1 * seq)
    a.advance(float(window))
    b.advance(float(window))
    assert_dags_equal(a.replicas.dags, b.replicas.dags, msg="advance:")
    sa, sb = a.converge(at_time=float(window) + 20.0), b.converge(at_time=float(window) + 20.0)
    assert sa == sb
    assert_dags_equal(a.replicas.dags, b.replicas.dags, msg="converge:")


@multidevice
@pytest.mark.parametrize("i", [0, 1, 15])
def test_compiled_read_of_sharded_replicas_matches_eager_slices(i):
    """The compiled gather over receiver-sharded replicas returns the eager
    slice's bits, and its result feeds the donated write."""
    rng = np.random.default_rng(7)
    mesh = mesh_lib.make_gossip_mesh(nodes=8)
    dags = mesh_lib.shard_replicas(random_stacked(rng, 16), mesh)
    rs = replica_lib.ReplicaSet(dags=dags, bank=jnp.zeros((CAP, 4)))
    got = replica_lib.read_replica(rs, i)
    assert_dags_equal(got, jax.tree_util.tree_map(lambda x: x[i], dags),
                      msg=f"read {i}:")
    want = jax.tree_util.tree_map(np.asarray, dags)
    rs = replica_lib.write_replica(rs, (i + 1) % 16, got)
    assert_dags_equal(
        replica_lib.read_replica(rs, (i + 1) % 16),
        jax.tree_util.tree_map(lambda x: x[i], want), msg=f"write {i}:",
    )


# ---------------------------------------------------------------------------
# e2e sim + single-device lane coverage (subprocess pins its own XLA flags)
# ---------------------------------------------------------------------------


@multidevice
def test_run_dagfl_gossip_mesh_matches_single_device():
    from repro.fl.experiments import default_dagfl_config, make_cnn_setup
    from repro.fl.systems import SimConfig, run_dagfl_gossip

    n = 16
    dcfg = default_dagfl_config(num_nodes=n)
    sim = SimConfig(iterations=12, eval_every=6, seed=0)
    mesh = mesh_lib.make_gossip_mesh(nodes=8)
    results = []
    for m in (None, mesh):
        task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=0)
        results.append(run_dagfl_gossip(
            task, nodes, dcfg, sim, gval,
            topology=topo.ring(n, seed=0),
            gossip=gossip_lib.GossipConfig(sync_period=1.0, seed=0),
            mesh=m,
        ))
    base, sharded = results
    np.testing.assert_array_equal(base.accs, sharded.accs)
    assert_dags_equal(base.extras["dag"], sharded.extras["dag"], msg="union:")
    assert base.extras["sync_rounds"] == sharded.extras["sync_rounds"]


def test_sharded_round_equivalence_in_subprocess():
    """Runs on every lane: forces 8 host devices in a child process and
    checks one advance+converge schedule bitwise against single-device."""
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import dag as dag_lib
        from repro.net import gossip as G, mesh as M, replica as R
        from repro.net import topology as topo
        assert jax.device_count() == 8, jax.device_count()
        CAP, K = 16, 2
        d = dag_lib.empty_dag(CAP, K, 17)
        d = dag_lib.publish(d, jnp.asarray(16, jnp.int32), jnp.float32(0.0),
            jnp.full((K,), dag_lib.NO_TX, jnp.int32), jnp.float32(0.5),
            jnp.float32(0.0), jnp.asarray(0, jnp.int32))
        def net(mesh):
            return G.GossipNetwork(d, bank=jnp.zeros((CAP, 4)),
                top=topo.ring(16, drop=0.2, seed=1),
                cfg=G.GossipConfig(sync_period=1.0, seed=5), mesh=mesh)
        a, b = net(None), net(M.make_gossip_mesh(nodes=2, model=4))
        for n_ in (a, b):
            dd = R.publish_local(n_.read(3), 1, jnp.asarray(3, jnp.int32),
                jnp.float32(0.1), jnp.full((K,), dag_lib.NO_TX, jnp.int32),
                jnp.float32(0.5), jnp.float32(0.0), jnp.asarray(1, jnp.int32))
            n_.write(3, dd)
        for i in (0, 3, 15):      # the compiled read of sharded replicas
            for f in dag_lib.DagState._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(b.read(i), f)),
                    np.asarray(getattr(b.replicas.dags, f)[i]), err_msg=f)
        a.advance(4.0); b.advance(4.0)
        assert a.converge(at_time=50.0) == b.converge(at_time=50.0)
        for f in dag_lib.DagState._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(a.replicas.dags, f)),
                np.asarray(getattr(b.replicas.dags, f)), err_msg=f)
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
