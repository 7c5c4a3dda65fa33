"""Per-node DAG replicas stacked into one vmappable pytree.

``ReplicaSet`` holds R = num_nodes copies of the ledger as a single
``DagState`` whose every leaf grew a leading replica axis — one pytree on
device, not R Python objects — so an anti-entropy round is one fused masked
reduction over the sender axis (see ``repro.net.gossip`` and
``repro.kernels.gossip_merge``) instead of a Python loop over merges. That
leading receiver axis is also the scaling axis: ``init_replicas(mesh=...)``
partitions it over a device mesh's "nodes" axis (``repro.net.mesh``), which
is what lets R grow past one device's memory.

The model bank's PAYLOAD stays stored once: rows are allocated from a
global publish sequence (``publish_local``), so a transaction occupies the
same slot on every replica and its bytes live once in the bank — a
content-addressed model store (replicating N full model banks would
multiply memory by N for no informational gain). What gossip propagates is
row *visibility* (a replica that has not received a row never reads its
bank slot) and — when the network is built with a
``bank.BankGossipConfig`` — per-node chunk *presence*: ``bank_state``
stacks each node's chunk-availability bitmap and in-flight link budgets
along the same leading replica axis, so payload transport is priced on the
Table-I bandwidth model while the store itself is never duplicated
(``repro.net.bank``). ``bank_state`` is None when the bank is not gossiped
— the PR-3 behavior, bitwise.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dag as dag_lib
from repro.core.dag import DagState
from repro.kernels import gossip_merge


class ReplicaSet(NamedTuple):
    dags: DagState      # every leaf has leading axis (R, ...)
    bank: Any           # shared model bank (repro.core.bank pytree)
    bank_state: Any = None   # per-node chunk transport (repro.net.bank
                             # BankState, leading axis R) — None when the
                             # bank is not gossiped

    @property
    def num_replicas(self) -> int:
        return int(self.dags.publisher.shape[0])


def init_replicas(
    dag: DagState, bank: Any, num_replicas: int, mesh=None
) -> ReplicaSet:
    """Every node starts from the same view (the genesis ledger).

    ``mesh`` (repro.net.mesh) places the stacked leaves with the leading
    receiver axis sharded over the mesh's "nodes" axis from the start: the
    broadcast runs jitted with sharded ``out_shardings``, so each device
    materializes only its R/shards receiver block — the whole point of the
    mesh is a stack too big for one device. The bank stays replicated
    either way (it is shared, see above).
    """

    def stack(d):
        return jax.tree_util.tree_map(
            lambda x: jnp.repeat(x[None], num_replicas, axis=0), d
        )

    if mesh is None:
        return ReplicaSet(dags=stack(dag), bank=bank)
    from repro.net import mesh as mesh_lib

    mesh_lib.validate_replica_mesh(num_replicas, mesh)
    stacked_like = jax.eval_shape(stack, dag)
    dags = jax.jit(
        stack, out_shardings=mesh_lib.replica_sharding(mesh, stacked_like)
    )(dag)
    return ReplicaSet(dags=dags, bank=bank)


@jax.jit
def _read_dags(dags: DagState, i) -> DagState:
    return jax.tree_util.tree_map(lambda x: x[i], dags)


def read_replica(rs: ReplicaSet, i) -> DagState:
    """Replica ``i``'s ledger as one compiled gather over the stacked leaves.

    ``i`` is a traced argument, so every node id shares one program per leaf
    structure and shape; the slices are the eager ``x[i]``'s bits. Nothing is
    donated (the commit writes the replicas afterwards) and nothing waits on
    the device.
    """
    if not isinstance(i, jax.Array):
        i = np.int32(i)   # one trace for Python and numpy ids alike
    return _read_dags(rs.dags, i)


@functools.partial(jax.jit, donate_argnums=0)
def _write_dags_donated(dags: DagState, i, dag: DagState) -> DagState:
    return jax.tree_util.tree_map(lambda x, v: x.at[i].set(v), dags, dag)


def write_replica(rs: ReplicaSet, i, dag: DagState) -> ReplicaSet:
    """Write replica ``i``'s rows in place.

    The stacked ``dags`` buffers are DONATED to the update, so each commit
    scatters one replica's rows into the existing allocation instead of
    copying the whole (R, cap, ...) pytree — arrays reachable from the
    ``rs`` passed in are invalid afterwards; use the returned set.
    """
    return rs._replace(dags=_write_dags_donated(rs.dags, i, dag))


def global_row(dag: DagState, seq):
    """(row, count watermark) for a globally-sequenced publish — THE row
    addressing rule replicas must share for ``dag.merge`` to reconcile by
    identity. Using the global sequence (not the replica-local ``count``)
    keeps the same transaction at the same slot on every replica; ``count``
    becomes a watermark, the highest sequence this replica has published
    past (merge max-combines it with what gossip brings in)."""
    seq = jnp.asarray(seq, jnp.int32)
    row = jnp.mod(seq, dag_lib.capacity_of(dag))
    return row, jnp.maximum(dag.count, seq + 1)


def publish_local(
    dag: DagState,
    seq,                # () int32 global publish sequence number
    publisher,
    time,
    approvals,
    accuracy,
    auth_tag,
    model_slot,
) -> DagState:
    """Publish into a replica at the globally-allocated row (``global_row``)."""
    row, new_count = global_row(dag, seq)
    return dag_lib.publish_at(
        dag, row, new_count, publisher, time, approvals, accuracy, auth_tag,
        model_slot,
    )


# ---------------------------------------------------------------------------
# Union view + divergence metrics
# ---------------------------------------------------------------------------


def merge_all(dags: DagState) -> DagState:
    """Fold ``dag.merge`` across the replica axis — the union ledger.

    Merge is commutative/associative/idempotent, so the fold order is
    irrelevant; the union is what an omniscient observer (the paper's
    external agent E) would see, and equals the shared-ledger state when the
    overlay is fully synchronized. Implemented as the same fused winner
    reduction the anti-entropy round uses (one receiver hearing every
    replica — the ``Rr=1`` case of ``kernels.gossip_merge.gossip_winner``), which
    is bitwise-equal to the sequential fold: the reduction's replica-0 tie
    preference is exactly the fold's first-element preference.
    """
    r = dags.publisher.shape[0]
    mask = jnp.ones((1, r), bool)
    src, _ = gossip_merge.gossip_winner(
        dags.publish_time, dags.publisher, dags.approval_count, mask
    )
    merged = dag_lib.merge_select(dags, src, mask=mask)
    return jax.tree_util.tree_map(lambda x: x[0], merged)


def missing_vs_union(dags: DagState, union: DagState = None) -> jnp.ndarray:
    """(R,) rows each replica has not yet seen relative to the union view —
    0 everywhere iff row visibility has fully converged. Pass a precomputed
    union to avoid re-folding the replicas."""
    if union is None:
        union = merge_all(dags)
    have = (dags.publisher == union.publisher[None]) & (
        dags.publish_time == union.publish_time[None]
    )
    have = have | (union.publisher[None] < 0)
    return jnp.sum((~have).astype(jnp.int32), axis=-1)


def missing_vs_peer(dags: DagState) -> jnp.ndarray:
    """(R, R) rows receiver i has not yet seen of what sender j holds.

    The pairwise form of ``missing_vs_union``: entry (i, j) counts the
    occupied rows of replica j whose identity (publisher, publish_time)
    replica i does not hold at the same global slot — how far i lags j
    specifically, not just the union. The diagonal is zero, a column is
    what the overlay still owes everyone from node j's view, and a row
    pinned high while the rest of its column drains is a receiver being
    starved (eclipse / partition / dead link) — the per-link series
    ``repro.obs`` samples (``staleness_link``). Rows are positionally
    aligned across replicas (``replica.global_row``), the same property
    ``missing_vs_union`` leans on.
    """
    p, t = dags.publisher, dags.publish_time
    have = (p[:, None, :] == p[None, :, :]) & (
        t[:, None, :] == t[None, :, :]
    )
    have = have | (p[None, :, :] < 0)
    return jnp.sum((~have).astype(jnp.int32), axis=-1)


def replicas_synced(dags: DagState) -> jnp.ndarray:
    """() bool — every replica leaf-identical to replica 0."""
    flags = [
        jnp.all(x == x[0:1]) for x in jax.tree_util.tree_leaves(dags)
    ]
    return jnp.all(jnp.stack(flags))


# Module-level jitted entry points: one trace per leaf structure/shape, no
# matter how many GossipNetwork instances a benchmark sweep constructs.
missing_vs_union_jit = jax.jit(missing_vs_union)
replicas_synced_jit = jax.jit(replicas_synced)
