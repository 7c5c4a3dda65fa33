"""Compile-only checks: every main-path Pallas kernel, at paper widths, for a
described (not attached) TPU v5e chip.

Interpret-mode tests cannot see what the chip's compiler refuses (block
shapes off the (8, 128) tile, too much fast memory, a kernel left for XLA
to partition over several chips). These tests lower each kernel with
``interpret=False`` against a described ``v5e:2x2`` topology and compile it
with the installed TPU compiler; nothing runs. Each asserts that the
compiled program holds the kernel (``tpu_custom_call``). The mesh cases
compile, with the replica set sharded over the four chips, the host-side
reads and the tick advance with telemetry armed: both reach kernels outside
the sharded round, which XLA refuses to partition.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and the other test workers must
collect the same tests without touching it.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core import dag as dag_lib
from repro.fl.tasks import CNNTask
from repro.kernels.chunk_transfer import chunk_dedup_pallas
from repro.kernels.delta_codec import BLOCK, quant_blocks_pallas, topk_blocks_pallas
from repro.kernels.event_pop import event_pop_pallas
from repro.kernels.fedavg import fedavg_pallas
from repro.kernels.gossip_merge import gossip_winner_pallas
from repro.kernels.hist_bincount import hist_bincount_pallas
from repro.kernels.model_distance import model_distance_pallas
from repro.net import bank as bank_lib
from repro.net import mesh as mesh_lib
from repro.net import replica as replica_lib

NODES, CAP, CHUNKS, ALPHA, BINS = 100, 192, 4, 5, 65   # the paper deployment


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2 host (cache off: an entry
    compiled for a described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _cnn_params() -> int:
    shapes = jax.eval_shape(CNNTask().init, jax.random.PRNGKey(0))
    return sum(math.prod(l.shape) for l in jax.tree_util.tree_leaves(shapes))


def _cases():
    """name -> (kernel, argument shapes); shapes built lazily per test."""
    f32, i32 = jnp.float32, jnp.int32
    keys = [((NODES, CAP), f32), ((NODES, CAP), i32), ((NODES, CAP), i32)]
    q = NODES * (NODES + 1)
    return {
        "gossip_winner": (
            lambda t, p, a, m: gossip_winner_pallas(t, p, a, m, interpret=False),
            lambda n: keys + [((NODES, NODES), bool)]),
        "gossip_winner_union": (
            lambda t, p, a, m: gossip_winner_pallas(t, p, a, m, interpret=False),
            lambda n: keys + [((1, NODES), bool)]),
        "gossip_winner_shard_block": (
            lambda t, p, a, m, o: gossip_winner_pallas(
                t, p, a, m, interpret=False, row_offset=o),
            lambda n: keys + [((NODES // 4, NODES), bool), ((), i32)]),
        "event_pop": (
            lambda t, k, s, v: event_pop_pallas(t, k, s, v, interpret=False),
            lambda n: [((q,), f32), ((q,), i32), ((q,), i32), ((q,), bool)]),
        "chunk_dedup": (
            lambda h, d: chunk_dedup_pallas(h, d, interpret=False),
            lambda n: [((NODES, CAP, CHUNKS), bool), ((CAP, CHUNKS), f32)]),
        "fedavg": (
            lambda w, m: fedavg_pallas(w, m, interpret=False),
            lambda n: [((ALPHA,), f32), ((ALPHA, n), f32)]),
        "model_distance": (
            lambda m: model_distance_pallas(m, interpret=False),
            lambda n: [((ALPHA, n), f32)]),
        "hist_bincount": (
            lambda i, w: hist_bincount_pallas(i, w, BINS, interpret=False),
            lambda n: [((NODES * CAP,), i32), ((NODES * CAP,), i32)]),
        "quant_blocks": (
            lambda x: quant_blocks_pallas(x, 127, interpret=False),
            lambda n: [((-(-n // BLOCK), BLOCK), f32)]),
        "topk_blocks": (
            lambda d: topk_blocks_pallas(d, 8, interpret=False),
            lambda n: [((-(-n // BLOCK), BLOCK), f32)]),
    }


@pytest.mark.parametrize("name", list(_cases()))
def test_kernel_compiles_for_v5e(v5e_2x2, name):
    kernel, shapes = _cases()[name]
    one_chip = SingleDeviceSharding(v5e_2x2[0])
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes(_cnn_params())]
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _mesh_reads(mesh):
    """name -> (replicated read, argument structs): the paper deployment's
    replica set and bank transport state with the receiver axis sharded."""
    def struct(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    row = jax.eval_shape(lambda: dag_lib.empty_dag(CAP, 2, NODES))
    dags = jax.tree_util.tree_map(
        lambda x: struct((NODES,) + x.shape, x.dtype, P(mesh_lib.NODES_AXIS)),
        row)
    row = jax.tree_util.tree_map(lambda x: struct(x.shape, x.dtype, P()), row)
    sharded = P(mesh_lib.NODES_AXIS)
    bstate = bank_lib.BankState(
        have=struct((NODES, CAP, CHUNKS), bool, sharded),
        credit=struct((NODES, NODES), jnp.float32, sharded),
        sent=struct((NODES, NODES), jnp.float32, sharded))
    have_row = struct((CAP, CHUNKS), bool, P())
    digest = struct((CAP, CHUNKS), jnp.float32, P())
    read = mesh_lib.replicated_jit
    return {
        "gate_view": (read(bank_lib.gate_view, mesh), (row, have_row, digest)),
        "missing_chunks": (read(bank_lib.missing_chunks, mesh, impl=None),
                           (dags, bstate, digest)),
        "merge_all": (read(replica_lib.merge_all, mesh), (dags,)),
    }


@pytest.mark.parametrize("name", ["gate_view", "missing_chunks", "merge_all"])
def test_mesh_read_compiles_for_v5e_2x2(v5e_2x2, monkeypatch, name):
    from repro.kernels import dispatch

    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)   # trace the TPU path
    mesh = mesh_lib.make_gossip_mesh(4, devices=v5e_2x2)
    fn, args = _mesh_reads(mesh)[name]
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


class _Captured(Exception):
    pass


def _armed_advance_args(bank: bool, obs_cfg):
    """The arguments of one tick advance of the paper deployment with
    telemetry armed, taken from a single-device network just before its
    dispatch (nothing runs)."""
    from repro.net import gossip as gossip_lib
    from repro.net import topology as topo
    from repro.net.bank import BankGossipConfig

    net = gossip_lib.GossipNetwork(
        dag_lib.empty_dag(CAP, 2, NODES + 1), bank=jnp.zeros((CAP, 8)),
        top=topo.full(NODES), cfg=gossip_lib.GossipConfig(sync_period=1.0),
        bank_cfg=BankGossipConfig(chunks_per_slot=CHUNKS) if bank else None,
        obs_cfg=obs_cfg)
    captured = []

    def capture(label, fn, *args):
        captured.append(args)
        raise _Captured

    net._dispatch = capture
    with pytest.raises(_Captured):
        net.advance(4.0)
    return captured[0]


@pytest.mark.parametrize("bank", [False, True], ids=["advance", "advance_bank"])
def test_armed_mesh_advance_compiles_for_v5e_2x2(v5e_2x2, monkeypatch, bank):
    """The tick advance sharded over the four chips with metrics, trace and
    histograms armed: the telemetry runs replicated beside the sharded
    round and reaches the union fold, chunk accounting and the bincount."""
    from repro.kernels import dispatch
    from repro.net import gossip as gossip_lib
    from repro.obs import HistConfig, ObsConfig

    obs_cfg = ObsConfig(hist=HistConfig())
    args = _armed_advance_args(bank, obs_cfg)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)   # trace the TPU path
    mesh = mesh_lib.make_gossip_mesh(4, devices=v5e_2x2)
    n_sharded = 2 if bank else 1       # the stacked dags (and BankState)
    specs = ([mesh_lib.replica_sharding(mesh, a) for a in args[:n_sharded]]
             + [jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), a)
                for a in args[n_sharded:]])
    structs = [jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), a, sp)
        for a, sp in zip(args, specs)]
    fn = (gossip_lib._advance_bank_jit("fused", None, mesh, obs_cfg) if bank
          else gossip_lib._advance_jit("fused", mesh, obs_cfg))
    compiled = fn.lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _event_advance_args(bank: bool):
    """The arguments of one event advance of the paper deployment (the
    cells' transport program), taken from a single-device network just
    before its dispatch (nothing runs)."""
    from repro.net import gossip as gossip_lib
    from repro.net import topology as topo
    from repro.net.bank import BankGossipConfig

    net = gossip_lib.GossipNetwork(
        dag_lib.empty_dag(CAP, 2, NODES + 1), bank=jnp.zeros((CAP, 8)),
        top=topo.full(NODES),
        cfg=gossip_lib.GossipConfig(sync_period=1.0, engine="events"),
        bank_cfg=BankGossipConfig(chunks_per_slot=CHUNKS) if bank else None)
    captured = []

    def capture(label, fn, *args):
        captured.append(args)
        raise _Captured

    net._dispatch = capture
    with pytest.raises(_Captured):
        net.advance(4.0)
    return captured[0]


@pytest.mark.parametrize("bank", [False, True],
                         ids=["advance_events", "advance_events_bank"])
def test_event_advance_compiles_for_v5e(v5e_2x2, monkeypatch, bank):
    """The event engine's one loop body on one chip, every optional layer
    off: the gossip winner and the event-queue pop reach the chip's
    compiler as kernels."""
    from repro.kernels import dispatch
    from repro.net import events

    args = _event_advance_args(bank)
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)   # trace the TPU path
    one_chip = SingleDeviceSharding(v5e_2x2[0])
    structs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        args)
    fn = (events._advance_events_bank_jit("fused", None) if bank
          else events._advance_events_jit("fused"))
    fn.clear_cache()      # a CPU trace at these shapes would be reused
    try:
        text = fn.lower(*structs).compile().as_text()
    finally:
        fn.clear_cache()
    assert text.count("tpu_custom_call") >= 2, "winner and pop kernels"
