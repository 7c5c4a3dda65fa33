"""Device-mesh placement for the gossip overlay: shard the receiver axis.

The ``ReplicaSet`` stacks N per-node DAG replicas along one leading receiver
axis (repro.net.replica); this module partitions that axis over the
``"nodes"`` axis of a device mesh so replica memory and per-tick sync FLOPs
scale with the device count instead of capping N on one device (the §III.A
many-node DAG layer actually living on many devices).

The sharded anti-entropy round (repro.net.gossip) is a ``shard_map`` over
the mesh: each shard all-gathers the sender rows once (THE collective of
the round — the fused winner rule made the whole round one masked reduction
plus a row gather, so sharding receivers turns it into a per-shard
reduction over the gathered sender axis), reduces winners for its own
receiver block, and writes back only its block. Any extra mesh axes (e.g. a
``model`` axis in a 2x4 mesh) are unused by gossip and simply replicate.

With the model bank gossiped (``repro.net.bank``), the sharded tick gains a
second, equally skinny collective: each shard dedups its own receivers'
chunk presence and all-gathers the resulting availability BITMAPS — never
payload bytes; the content-addressed store stays shared — then selects its
block's transfers against the gathered sender availability. The per-node
``BankState`` leaves (presence bitmap, link credit, byte meter) all lead
with the receiver axis, so the same ``replica_specs`` placement rule shards
them.

``make_gossip_mesh`` builds the canonical ("nodes", "model") mesh; on CPU
runners the multi-device path needs
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (what the CI
8-device lane pins). ``mesh=None`` everywhere preserves the single-device
paths bitwise.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.specs import replica_specs, to_shardings

NODES_AXIS = "nodes"


def make_gossip_mesh(
    nodes: Optional[int] = None, model: int = 1, devices=None
) -> Mesh:
    """A ("nodes", "model") mesh; gossip shards receivers over "nodes" only.

    ``nodes=None`` spends every visible device on the nodes axis. A 2x4 mesh
    (nodes=2, model=4) and an 8x1 mesh sync identically — the model axis is
    replicated by the gossip layer; it exists so one mesh can serve both the
    sharded overlay and tensor-parallel model work (repro.sharding).
    """
    devices = np.asarray(jax.devices() if devices is None else devices)
    if nodes is None:
        nodes = devices.size // model
    if nodes * model > devices.size:
        raise ValueError(
            f"mesh {nodes}x{model} needs {nodes * model} devices, "
            f"only {devices.size} visible"
        )
    return Mesh(
        devices[: nodes * model].reshape(nodes, model), (NODES_AXIS, "model")
    )


def nodes_axis_size(mesh: Optional[Mesh]) -> int:
    return 1 if mesh is None else int(mesh.shape[NODES_AXIS])


def validate_replica_mesh(num_nodes: int, mesh: Mesh) -> None:
    """The receiver axis must tile exactly over the nodes axis — an uneven
    split would need padded phantom replicas inside every collective; pick
    an overlay size divisible by the nodes axis instead."""
    if NODES_AXIS not in mesh.axis_names:
        raise ValueError(
            f"gossip mesh needs a {NODES_AXIS!r} axis, got {mesh.axis_names}"
        )
    shards = nodes_axis_size(mesh)
    if num_nodes % shards != 0:
        raise ValueError(
            f"num_nodes={num_nodes} not divisible by the {NODES_AXIS!r} "
            f"axis ({shards}); resize the overlay or the mesh"
        )


def replica_sharding(mesh: Mesh, tree: Any) -> Any:
    """NamedSharding pytree: every leaf's leading receiver axis -> nodes."""
    return to_shardings(mesh, replica_specs(tree, NODES_AXIS))


def shard_replicas(dags: Any, mesh: Mesh) -> Any:
    """Place stacked replicas with the receiver axis split over "nodes"."""
    return jax.device_put(dags, replica_sharding(mesh, dags))


def replicate(x: Any, mesh: Mesh) -> Any:
    """Place overlay-wide arrays (adjacency, drop, strides) fully replicated
    so the jitted sync loops see one committed layout per mesh."""
    return jax.device_put(x, NamedSharding(mesh, P()))


def replicated(fn, mesh: Mesh) -> Any:
    """``fn`` run whole on every device of ``mesh``.

    XLA cannot partition a Pallas kernel: a multi-device program may call
    one only inside a ``shard_map``. Code over the sharded replicas that
    reaches a kernel outside the sharded round (the union fold, view
    gating, chunk accounting, telemetry) therefore runs as a ``shard_map``
    whose inputs are gathered in full, each device computing the same
    replicated result. ``fn`` takes arrays only, positionally.
    """
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


@functools.lru_cache(maxsize=None)
def replicated_jit(fn, mesh: Optional[Mesh], **static) -> Any:
    """``replicated(fn, mesh)`` jitted, for host-side reads (``mesh=None``:
    plain ``jax.jit(fn)``); ``static`` binds keyword arguments that select
    code paths (e.g. ``impl``)."""
    body = functools.partial(fn, **static) if static else fn
    return jax.jit(body if mesh is None else replicated(body, mesh))
