"""Model bank: transaction payloads as one stacked pytree.

Slot i of every leaf is transaction i's model. Keeping payloads stacked
(instead of a python list) lets tip validation vmap over candidates, lets
Eq.-1 aggregation gather just the ``k`` chosen slots, and gives
checkpointing a single pytree to serialize.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def init_bank(template: Any, slots: int) -> Any:
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros((slots,) + p.shape, p.dtype), template
    )


def bank_write(bank: Any, slot: jnp.ndarray, params: Any) -> Any:
    return jax.tree_util.tree_map(lambda b, p: b.at[slot].set(p), bank, params)


def bank_read(bank: Any, slot: jnp.ndarray) -> Any:
    return jax.tree_util.tree_map(lambda b: b[slot], bank)


@jax.jit
def bank_gather(bank: Any, slots: jnp.ndarray) -> Any:
    """slots (k,) -> stacked params with leading k (invalid slots clamp to 0).

    One dynamic slice per slot, not a gather: XLA's TPU backend lowers a
    gather on the slot axis by first copying the whole bank, while a
    dynamic slice reads only its slot. Jitted, so an eager caller dispatches
    one program rather than k slices and a stack per leaf; under an outer
    jit it is inlined.
    """
    safe = jnp.maximum(slots, 0)
    return jax.tree_util.tree_map(
        lambda b: jnp.stack([jax.lax.dynamic_index_in_dim(b, safe[i], keepdims=False)
                             for i in range(safe.shape[0])]), bank)


def bank_average(bank: Any, slots: jnp.ndarray, weights: jnp.ndarray) -> Any:
    """Eq. (1) over bank slots: gather the k chosen slots, weighted sum over k.

    slots (k,) int32 (NO_TX = -1 entries get zero weight); weights (k,) f32.
    Only the k chosen models are read, so the cost does not grow with the
    bank's capacity, and a non-finite value in an unchosen slot stays out.
    The sum over k is float32 at HIGHEST precision, whatever the process's
    default matmul precision.
    """
    w = jnp.where(slots >= 0, weights, 0.0).astype(jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-9)       # renormalize over VALID slots

    def avg(b):
        return jnp.tensordot(w, b.astype(jnp.float32), axes=1,
                             precision=jax.lax.Precision.HIGHEST).astype(b.dtype)

    return jax.tree_util.tree_map(avg, bank_gather(bank, slots))


def auth_checksum(params: Any) -> jnp.ndarray:
    """Cheap integrity tag standing in for the RSA signature (DESIGN.md §3).

    A fixed pseudo-random projection of every leaf — any bit flip in the
    payload moves the tag; impersonation (publishing someone else's params
    under a new tag) is what the simulator's lazy nodes do.
    """
    total = jnp.zeros((), jnp.float32)
    for i, leaf in enumerate(jax.tree_util.tree_leaves(params)):
        flat = leaf.reshape(-1).astype(jnp.float32)
        idx = jnp.arange(flat.shape[0], dtype=jnp.float32)
        proj = jnp.cos(idx * (0.618033988749895 + 0.001 * i))
        total = total + jnp.dot(flat, proj)
    return total
