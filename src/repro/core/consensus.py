"""DAG-FL consensus: one full Algorithm-2 iteration as a jittable function.

Stage 1  select <= alpha tips within tau_max          (dag.select_tips)
Stage 2  authenticate + validate their models          (validation)
Stage 3  FedAvg the k best, train beta epochs locally  (aggregation + train_fn)
Stage 4  publish the new transaction with k approvals  (dag.publish)

``make_dagfl_iteration`` closes over the task's ``eval_fn(params, batch)``
and ``train_fn(params, batch, key) -> (params, metrics)`` so the same
consensus drives the paper's CNN/LSTM tasks, the assigned architectures,
and the distributed runtime.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import DagFLConfig
from repro.core import aggregation as agg
from repro.core import bank as bank_lib
from repro.core import dag as dag_lib
from repro.core import validation as val_lib


class IterationOut(NamedTuple):
    dag: dag_lib.DagState
    bank: Any
    new_accuracy: jnp.ndarray       # accuracy of the freshly published model
    chosen_rows: jnp.ndarray        # (k,) dag rows approved
    num_tips_seen: jnp.ndarray


class Prepared(NamedTuple):
    """Stages 1-3 output, awaiting stage-4 publication at completion time.

    Decoupling select(t0) from publish(t1 = t0 + h) is what lets tips
    accumulate to the paper's L0 = k*lambda*h/(k-1) equilibrium — iterations
    in flight select overlapping tip sets (Fig. 4's t1/t2 timeline).
    """

    new_params: Any
    chosen_rows: jnp.ndarray
    new_accuracy: jnp.ndarray
    num_tips_seen: jnp.ndarray


def make_dagfl_iteration(
    cfg: DagFLConfig,
    eval_fn: Callable[[Any, Any], jnp.ndarray],
    train_fn: Callable[[Any, Any, jnp.ndarray], Any],
    weighted: bool = False,
):
    """Returns iteration(dag, bank, node_id, now, key, train_batch, val_batch)."""
    validator = val_lib.make_validator(eval_fn)

    def iteration(
        dag, bank, node_id, now, key, train_batch, val_batch, node_bias=None
    ) -> IterationOut:
        k_sel, k_train = jax.random.split(key)

        # --- stage 1: tip selection -------------------------------------
        rows, nvalid = dag_lib.select_tips(
            dag, k_sel, cfg.alpha, now, cfg.tau_max, node_bias=node_bias
        )
        slots = jnp.where(rows >= 0, dag.model_slot[jnp.maximum(rows, 0)], -1)

        # --- stage 2: authenticate + validate ---------------------------
        auth_ok = val_lib.authenticate(dag.auth_tag, bank, slots)
        accs = validator(bank, slots, val_batch)
        accs = jnp.where(auth_ok, accs, -jnp.inf)

        # --- stage 3: top-k FedAvg + local training ----------------------
        chosen_slots, top_pos, top_acc = val_lib.select_top_k(accs, slots, cfg.k)
        chosen_rows = jnp.where(
            jnp.isfinite(top_acc), rows[top_pos], dag_lib.NO_TX
        ).astype(jnp.int32)
        n_chosen = jnp.sum(chosen_slots >= 0)

        if weighted:
            stale = now - dag.publish_time[jnp.maximum(chosen_rows, 0)]
            weights = agg.staleness_accuracy_weights(
                jnp.where(jnp.isfinite(top_acc), top_acc, 0.0), stale, cfg.tau_max
            )
        else:
            weights = agg.uniform_weights(cfg.k)

        aggregated = bank_lib.bank_average(bank, chosen_slots, weights)
        # no usable tips -> continue from the most recent model (genesis early on)
        last_slot = dag.model_slot[jnp.mod(dag.count - 1, dag_lib.capacity_of(dag))]
        fallback = bank_lib.bank_read(bank, jnp.maximum(last_slot, 0))
        global_model = jax.tree_util.tree_map(
            lambda a, f: jnp.where(n_chosen > 0, a, f), aggregated, fallback
        )

        new_params = global_model
        for _ in range(cfg.beta):                          # beta local epochs
            new_params, _ = train_fn(new_params, train_batch, k_train)

        # --- stage 4: publish --------------------------------------------
        new_acc = eval_fn(new_params, val_batch).astype(jnp.float32)
        tag = bank_lib.auth_checksum(new_params)
        slot = jnp.mod(dag.count, dag_lib.capacity_of(dag))
        bank = bank_lib.bank_write(bank, slot, new_params)
        dag = dag_lib.publish(
            dag,
            jnp.asarray(node_id, jnp.int32),
            jnp.asarray(now, jnp.float32),
            chosen_rows,
            new_acc,
            tag,
            slot,
        )
        return IterationOut(dag, bank, new_acc, chosen_rows, nvalid)

    return iteration


def make_dagfl_stages(
    cfg: DagFLConfig,
    eval_fn: Callable[[Any, Any], jnp.ndarray],
    train_fn: Callable[[Any, Any, jnp.ndarray], Any],
    weighted: bool = False,
):
    """Split Algorithm 2 into prepare (stages 1-3, at iteration START) and
    commit (stage 4, at COMPLETION). Returns (prepare_fn, commit_fn)."""
    validator = val_lib.make_validator(eval_fn)

    def prepare(dag, bank, now, key, train_batch, val_batch, node_bias=None) -> Prepared:
        # named scopes (``dagfl/<stage>``) tag each stage's ops in the
        # compiled program's metadata, so a device profile can split
        # prepare's time by stage; they add no operations
        with jax.named_scope("dagfl/select"):
            k_sel, k_train = jax.random.split(key)
            rows, nvalid = dag_lib.select_tips(
                dag, k_sel, cfg.alpha, now, cfg.tau_max, node_bias=node_bias
            )
            slots = jnp.where(rows >= 0, dag.model_slot[jnp.maximum(rows, 0)], -1)
        with jax.named_scope("dagfl/validate"):
            auth_ok = val_lib.authenticate(dag.auth_tag, bank, slots)
            accs = jnp.where(auth_ok, validator(bank, slots, val_batch), -jnp.inf)
            chosen_slots, top_pos, top_acc = val_lib.select_top_k(accs, slots, cfg.k)
            chosen_rows = jnp.where(
                jnp.isfinite(top_acc), rows[top_pos], dag_lib.NO_TX
            ).astype(jnp.int32)
            n_chosen = jnp.sum(chosen_slots >= 0)

        with jax.named_scope("dagfl/aggregate"):
            if weighted:
                stale = now - dag.publish_time[jnp.maximum(chosen_rows, 0)]
                weights = agg.staleness_accuracy_weights(
                    jnp.where(jnp.isfinite(top_acc), top_acc, 0.0), stale, cfg.tau_max
                )
            else:
                weights = agg.uniform_weights(cfg.k)
            aggregated = bank_lib.bank_average(bank, chosen_slots, weights)
            last_slot = dag.model_slot[jnp.mod(dag.count - 1, dag_lib.capacity_of(dag))]
            fallback = bank_lib.bank_read(bank, jnp.maximum(last_slot, 0))
            global_model = jax.tree_util.tree_map(
                lambda a, f: jnp.where(n_chosen > 0, a, f), aggregated, fallback
            )
        with jax.named_scope("dagfl/train"):
            new_params = global_model
            for _ in range(cfg.beta):
                new_params, _ = train_fn(new_params, train_batch, k_train)
        with jax.named_scope("dagfl/validate"):
            new_acc = eval_fn(new_params, val_batch).astype(jnp.float32)
        return Prepared(new_params, chosen_rows, new_acc, nvalid)

    return prepare, commit_prepared


def commit_prepared(dag, bank, node_id, t_publish, prepared: Prepared,
                    slot=None, new_count=None):
    """Stage-4 publication of a ``Prepared`` iteration — the single commit
    body shared by every runtime.

    Default (``slot=None``): append at the ledger-local row
    ``count % capacity`` (the shared-ledger runtime). Gossip replicas
    (``repro.net``) instead pass a slot and count watermark derived from the
    global publish sequence, so the same transaction lands in the same slot
    on every replica.
    """
    if slot is None:
        slot = jnp.mod(dag.count, dag_lib.capacity_of(dag))
        new_count = dag.count + 1
    elif new_count is None:
        raise ValueError("commit_prepared: slot and new_count go together "
                         "(see repro.net.replica.global_row)")
    with jax.named_scope("dagfl/commit"):
        tag = bank_lib.auth_checksum(prepared.new_params)
        bank = bank_lib.bank_write(bank, slot, prepared.new_params)
        dag = dag_lib.publish_at(
            dag,
            slot,
            new_count,
            jnp.asarray(node_id, jnp.int32),
            jnp.asarray(t_publish, jnp.float32),
            prepared.chosen_rows,
            prepared.new_accuracy,
            tag,
            slot,
        )
    return dag, bank
