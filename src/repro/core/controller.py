"""Algorithm 1 — DAG-FL Controlling, run by the external agent E.

E is a host-side smart-contract analogue: it publishes the genesis
transaction, periodically reconstructs a candidate target model from the
best-k tips of its local DAG, and broadcasts the end signal once
ACC_t >= ACC_0.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import DagFLConfig
from repro.core import aggregation as agg
from repro.core import bank as bank_lib
from repro.core import dag as dag_lib
from repro.core import validation as val_lib


def host_read(label: str, x):
    """Blocking device->host read of ``x``; ``label`` names the read for
    callers that count them (``GossipNetwork._fetch``)."""
    return jax.device_get(x)


@dataclass
class ControllerState:
    dag: dag_lib.DagState
    bank: Any
    done: bool = False
    best_accuracy: float = 0.0
    target_model: Any = None
    checks: int = 0


class Controller:
    """External agent E (Algorithm 1)."""

    def __init__(
        self,
        cfg: DagFLConfig,
        eval_fn: Callable[[Any, Any], jnp.ndarray],
        target_accuracy: Optional[float] = None,
    ):
        self.cfg = cfg
        self.eval_fn = eval_fn
        self.validator = val_lib.make_validator(eval_fn)
        self.acc0 = target_accuracy if target_accuracy is not None else cfg.target_accuracy

    def genesis(self, init_params: Any, val_batch, capacity: Optional[int] = None) -> ControllerState:
        """Initialize the ledger with the initial model transaction."""
        cap = capacity or self.cfg.capacity
        dag = dag_lib.empty_dag(cap, self.cfg.k, self.cfg.num_nodes + 1)
        bank = bank_lib.init_bank(init_params, cap)
        bank = bank_lib.bank_write(bank, jnp.asarray(0), init_params)
        acc = self.eval_fn(init_params, val_batch)
        dag = dag_lib.publish(
            dag,
            jnp.asarray(self.cfg.num_nodes, jnp.int32),     # E's node id
            jnp.asarray(0.0, jnp.float32),
            jnp.full((self.cfg.k,), dag_lib.NO_TX, jnp.int32),
            jnp.asarray(acc, jnp.float32),
            bank_lib.auth_checksum(init_params),
            jnp.asarray(0, jnp.int32),
        )
        return ControllerState(dag=dag, bank=bank)

    def check(self, state: ControllerState, key, now: float, val_batch,
              fetch: Callable[[str, Any], Any] = None) -> ControllerState:
        """One Algorithm-1 loop body: validate alpha tips, build omega_0,
        test ACC_t >= ACC_0.

        ``fetch(label, x)`` makes the check's two blocking device->host
        reads (``host_read`` by default; the gossip driver passes its
        counting funnel, ``GossipNetwork._fetch``)."""
        fetch = fetch or host_read
        rows, _ = dag_lib.select_tips(
            state.dag, key, self.cfg.alpha, jnp.asarray(now, jnp.float32), self.cfg.tau_max
        )
        slots = jnp.where(rows >= 0, state.dag.model_slot[jnp.maximum(rows, 0)], -1)
        accs = self.validator(state.bank, slots, val_batch)
        chosen, _, top_acc = val_lib.select_top_k(accs, slots, self.cfg.k)
        n_ok = int(fetch("check", jnp.sum(chosen >= 0)))
        if n_ok == 0:
            state.checks += 1
            return state
        model = bank_lib.bank_average(
            state.bank, chosen, agg.uniform_weights(self.cfg.k)
        )
        acc_t = float(fetch("check", self.eval_fn(model, val_batch)))
        state.checks += 1
        if acc_t > state.best_accuracy:
            state.best_accuracy = acc_t
            state.target_model = model
        if acc_t >= self.acc0:
            state.done = True                               # end signal to D
        return state
