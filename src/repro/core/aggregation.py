"""Eq.-(1) FederatedAveraging and the §VI.C weighted extension.

Three interchangeable implementations of the same aggregation:
  * ``fedavg_pytree``   — tree_map weighted sum (clear, autodiff-safe),
  * ``fedavg_flat``     — the Pallas kernel over flattened params (TPU path),
  * ``bank_average``    — gathers the k chosen slots of the model bank and
                          contracts over k (lives in repro.core.bank).
All are cross-checked in tests.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops


def uniform_weights(k: int) -> jnp.ndarray:
    """Paper default: n_i = 1/k."""
    return jnp.full((k,), 1.0 / k, jnp.float32)


def fedavg_pytree(stacked: Any, weights: jnp.ndarray) -> Any:
    """stacked: pytree with leading k axis; weights (k,) summing to 1."""

    def avg(leaf):
        w = weights.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(jnp.float32)
        return jnp.sum(leaf.astype(jnp.float32) * w, axis=0).astype(leaf.dtype)

    return jax.tree_util.tree_map(avg, stacked)


def flatten_params(params: Any) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(params)
    return jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])


def unflatten_like(flat: jnp.ndarray, template: Any) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(template)
    out = []
    ofs = 0
    for l in leaves:
        out.append(flat[ofs : ofs + l.size].reshape(l.shape).astype(l.dtype))
        ofs += l.size
    return jax.tree_util.tree_unflatten(treedef, out)


def fedavg_flat(stacked: Any, weights: jnp.ndarray) -> Any:
    """Pallas-kernel path: flatten the k models, run the tiled kernel."""
    template = jax.tree_util.tree_map(lambda l: l[0], stacked)
    flat = jax.vmap(flatten_params)(stacked)              # (k, N)
    out = kops.fedavg(weights, flat)
    return unflatten_like(out, template)


def staleness_accuracy_weights(
    accuracies: jnp.ndarray,      # (k,) f32
    staleness: jnp.ndarray,       # (k,) f32 seconds
    tau_max: float,
    temperature: float = 4.0,
) -> jnp.ndarray:
    """§VI.C weighted aggregation: fresher + more accurate tips weigh more.

    w_i ∝ softmax(temperature * acc_i) * (1 - staleness_i / (2*tau_max)).
    Reduces to ~uniform when accuracies/staleness are equal.
    """
    a = jax.nn.softmax(temperature * accuracies)
    fresh = jnp.clip(1.0 - staleness / (2.0 * tau_max), 0.1, 1.0)
    w = a * fresh
    return w / jnp.maximum(jnp.sum(w), 1e-9)
