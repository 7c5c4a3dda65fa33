"""Eq.-(1) aggregation: three implementations agree + algebraic properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import aggregation as agg
from repro.core import bank as bank_lib


def rand_tree(key, k=None):
    k1, k2, k3 = jax.random.split(key, 3)
    shape = lambda s: (k,) + s if k else s
    return {
        "a": jax.random.normal(k1, shape((17, 5))),
        "b": {"c": jax.random.normal(k2, shape((3, 4, 2))), "d": jax.random.normal(k3, shape((11,)))},
    }


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), k=st.integers(2, 6))
def test_pytree_flat_bank_agree(seed, k):
    key = jax.random.PRNGKey(seed)
    stacked = rand_tree(key, k)
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (k,)))

    out_tree = agg.fedavg_pytree(stacked, w)
    out_flat = agg.fedavg_flat(stacked, w)
    bank = stacked
    out_bank = bank_lib.bank_average(bank, jnp.arange(k), w)

    for a, b in zip(jax.tree_util.tree_leaves(out_tree), jax.tree_util.tree_leaves(out_flat)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(out_tree), jax.tree_util.tree_leaves(out_bank)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_convex_combination_bounds(seed):
    """FedAvg output lies within [min, max] of the inputs, element-wise."""
    key = jax.random.PRNGKey(seed)
    stacked = jax.random.normal(key, (4, 50))
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (4,)))
    out = agg.fedavg_pytree(stacked, w)
    assert bool(jnp.all(out <= jnp.max(stacked, 0) + 1e-6))
    assert bool(jnp.all(out >= jnp.min(stacked, 0) - 1e-6))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_permutation_invariance(seed):
    key = jax.random.PRNGKey(seed)
    stacked = jax.random.normal(key, (5, 31))
    w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (5,)))
    perm = jax.random.permutation(jax.random.fold_in(key, 2), 5)
    out1 = agg.fedavg_pytree(stacked, w)
    out2 = agg.fedavg_pytree(stacked[perm], w[perm])
    np.testing.assert_allclose(out1, out2, rtol=1e-5, atol=1e-6)


def test_identity_when_single_model():
    x = jnp.arange(12.0).reshape(3, 4)
    stacked = x[None]
    out = agg.fedavg_pytree(stacked, jnp.ones((1,)))
    np.testing.assert_allclose(out, x)


def test_bank_average_skips_invalid_and_renormalizes():
    bank = jnp.stack([jnp.ones((6,)), 3 * jnp.ones((6,)), 100 * jnp.ones((6,))])
    out = bank_lib.bank_average(bank, jnp.asarray([0, 1, -1]), jnp.full((3,), 1 / 3))
    np.testing.assert_allclose(out, 2 * jnp.ones((6,)), rtol=1e-6)


def _onehot_bank_average(bank, slots, weights):
    """Eq. (1) as a one-hot product over every slot of the bank (the oracle)."""
    n = jax.tree_util.tree_leaves(bank)[0].shape[0]
    w = jnp.where(slots >= 0, weights, 0.0).astype(jnp.float32)
    w = w / jnp.maximum(jnp.sum(w), 1e-9)
    coeff = jnp.sum(jax.nn.one_hot(jnp.maximum(slots, 0), n) * w[:, None], axis=0)
    return jax.tree_util.tree_map(
        lambda b: jnp.tensordot(coeff, b, axes=1, precision="highest"), bank)


@pytest.mark.parametrize("capacity", [8, 57, 192])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("slots", [[3, 5], [2, -1], [4, 4, 1], [-1, 6, 0, -1]],
                         ids=["distinct", "no_tx", "repeat", "no_tx_and_slot0"])
def test_bank_average_matches_onehot_oracle(capacity, weighted, slots):
    key = jax.random.PRNGKey(capacity)
    bank = rand_tree(key, capacity)
    slots = jnp.asarray(slots, jnp.int32)
    k = slots.shape[0]
    w = (jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (k,)))
         if weighted else agg.uniform_weights(k))
    with jax.default_matmul_precision("highest"):
        out = bank_lib.bank_average(bank, slots, w)
    want = _onehot_bank_average(bank, slots, w)
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("poison", [np.nan, np.inf])
def test_bank_average_ignores_nonfinite_unchosen_slot(poison):
    """A non-finite model in a slot nobody chose does not reach the aggregate
    (a one-hot product would spread it: 0 x NaN is NaN)."""
    bank = rand_tree(jax.random.PRNGKey(7), 16)
    poisoned = jax.tree_util.tree_map(lambda b: b.at[9].set(poison), bank)
    slots, w = jnp.asarray([2, 11, -1]), agg.uniform_weights(3)
    clean = bank_lib.bank_average(bank, slots, w)
    out = bank_lib.bank_average(poisoned, slots, w)
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(clean)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_array_equal(a, b)


def test_bank_average_cost_does_not_grow_with_capacity():
    """The compiled aggregate reads k slots, not the whole bank: its FLOPs at
    192 slots match those at 8."""
    template = {"w": jnp.zeros((392, 512)), "b": jnp.zeros((512,))}
    slots, w = jnp.asarray([1, 5]), agg.uniform_weights(2)

    def flops(capacity):
        bank = bank_lib.init_bank(template, capacity)
        return jax.jit(bank_lib.bank_average).lower(bank, slots, w).compile().cost_analysis()["flops"]

    small, large = flops(8), flops(192)
    params = 392 * 512 + 512
    assert small <= 4 * len(slots) * params, small     # O(k P), not O(capacity P)
    assert large <= 1.05 * small, (small, large)


@pytest.mark.parametrize("fn", ["bank_gather", "bank_average"])
def test_bank_reads_do_not_grow_with_capacity(fn):
    """Reading k slots moves k slots' bytes: the compiled bytes at 192 slots
    match those at 8. (A gather on the slot axis is charged the whole bank,
    and on a TPU copies it.)"""
    template = {"w": jnp.zeros((392, 512)), "b": jnp.zeros((512,))}
    slots, w = jnp.asarray([1, 5]), agg.uniform_weights(2)
    args = (slots, w) if fn == "bank_average" else (slots,)

    def bytes_accessed(capacity):
        bank = bank_lib.init_bank(template, capacity)
        return jax.jit(getattr(bank_lib, fn)).lower(bank, *args).compile().cost_analysis()[
            "bytes accessed"]

    small, large = bytes_accessed(8), bytes_accessed(192)
    params = 392 * 512 + 512
    assert small <= 4 * 4 * len(slots) * params, small   # f32 bytes of O(k) models
    assert large <= 1.05 * small, (small, large)


def test_bank_gather_is_one_program_for_eager_callers():
    """An eager caller (the agent's check) dispatches one compiled program for
    the candidates, traced once per shape, whatever slots it reads."""
    bank = rand_tree(jax.random.PRNGKey(3), 12)
    before = bank_lib.bank_gather._cache_size()
    for slots in ([0, 4, 11], [-1, 2, 2], [7, 7, -1]):
        out = bank_lib.bank_gather(bank, jnp.asarray(slots, jnp.int32))
        safe = np.maximum(slots, 0)
        for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(bank)):
            np.testing.assert_array_equal(a, np.asarray(b)[safe])
    assert bank_lib.bank_gather._cache_size() - before == 1


def test_bank_average_sums_at_highest_precision():
    """The sum over k is pinned to HIGHEST, as the reference's is, whatever
    the process's default matmul precision."""
    bank = bank_lib.init_bank({"w": jnp.zeros((3, 4))}, 8)
    with jax.default_matmul_precision("bfloat16"):
        text = jax.jit(bank_lib.bank_average).lower(
            bank, jnp.asarray([1, -1]), agg.uniform_weights(2)).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert dots and all("precision = [HIGHEST, HIGHEST]" in d for d in dots), dots


def test_staleness_accuracy_weights():
    acc = jnp.asarray([0.9, 0.5, 0.9])
    stale = jnp.asarray([0.0, 0.0, 19.0])
    w = agg.staleness_accuracy_weights(acc, stale, tau_max=20.0)
    np.testing.assert_allclose(jnp.sum(w), 1.0, rtol=1e-6)
    assert w[0] > w[1]          # higher accuracy wins
    assert w[0] > w[2]          # fresher wins at equal accuracy


def test_auth_checksum_detects_change():
    key = jax.random.PRNGKey(0)
    tree = rand_tree(key)
    t1 = bank_lib.auth_checksum(tree)
    tree2 = jax.tree_util.tree_map(lambda x: x, tree)
    tree2["a"] = tree2["a"].at[0, 0].add(0.5)
    t2 = bank_lib.auth_checksum(tree2)
    assert abs(float(t1 - t2)) > 1e-6
