"""DeepSeek-V2-Lite's block as a federated LoRA payload, at a small size.

The program's MLA (YaRN), gating, held-expert MoE and adapters against the
benchmark's plain reference (``benchmarks/chip/tasks/dsv2lite_lora.py``,
which shares no code with ``src/``) on seeded random weights: hidden 64,
one dense and one MoE layer, 8 routed experts of which 4 are held, top-2.
"""
import copy
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.fl import systems
from repro.fl import tasks as tasks_lib
from repro.models import moe as moe_lib
from repro.models.layers import yarn_freqs, yarn_mscale
from repro.models.mla import softmax_scale
from repro.models.transformer import Model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "chip")

TINY = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 2, "num_attention_heads": 2, "num_key_value_heads": 2,
        "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "n_routed_experts": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "vocab_size": 128}


@pytest.fixture(scope="module")
def ref():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_task_dsv2lite_lora_t", os.path.join(BENCH, "tasks", "dsv2lite_lora.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    with open(os.path.join(BENCH, "configs", "dsv2lite-lora.json")) as f:
        m = copy.deepcopy(json.load(f)["model"])
    m["arch"].update(TINY)
    m.update(router_experts=8, expert_offset=2)
    m["lora"].update(rank=4, alpha=8)
    return m


@pytest.fixture(scope="module")
def task(ref, model):
    t = ref.program_task(tasks_lib, model)
    t.frozen                  # the base is built once, outside any trace
    return t


def _tokens(seed=0, b=2, s=12):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0, TINY["vocab_size"])


def _trained(task, seed=3):
    """Adapters with ``b`` nonzero, so every adapter leaf has a gradient."""
    ad = task.init(jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(x.size), x.shape), ad)


def test_program_matches_reference_logits_loss_and_gradients(ref, model, task):
    ref.use(model)
    ad = _trained(task)
    batch = {"tokens": _tokens()}
    hi = jax.lax.Precision.HIGHEST
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(jax.jit(task.logits)(ad, batch["tokens"]),
                                   jax.jit(ref.logits, static_argnums=2)(ad, batch, hi),
                                   rtol=2e-5, atol=2e-5)
        lp, gp = jax.jit(jax.value_and_grad(task.loss))(ad, batch)
        lr, gr = jax.jit(jax.value_and_grad(ref.loss), static_argnums=2)(ad, batch, hi)
    np.testing.assert_allclose(lp, lr, rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gr)):
        assert float(jnp.linalg.norm(b)) > 0
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    assert task.eval_fn(ad, batch) == jax.jit(ref.accuracy, static_argnums=2)(ad, batch, hi)


def test_genesis_adapters_and_base_match_the_reference_bitwise(ref, model, task):
    ad_p = task.init(jax.random.PRNGKey(7))
    ad_r = ref.init(jax.random.PRNGKey(7), model)
    assert jax.tree_util.tree_structure(ad_p) == jax.tree_util.tree_structure(ad_r)
    for a, b in zip(jax.tree_util.tree_leaves(ad_p), jax.tree_util.tree_leaves(ad_r)):
        assert np.array_equal(a, b)
    from repro.models.lora import path_name

    # the reference draws its copy inside its jitted programs, keyed on the batch
    want = jax.jit(lambda t: ref.frozen(model, t))(_tokens())
    got = {path_name(p): v for p, v in jax.tree_util.tree_flatten_with_path(task.frozen)[0]}
    assert set(got) == set(want)
    for k in want:
        assert np.array_equal(got[k], want[k]), k


def test_zero_b_leaves_the_base_output(task):
    ad = task.init(jax.random.PRNGKey(1))
    tok = _tokens(1)
    base, _ = Model(task.cfg, remat=False).forward(task.frozen, tok)
    assert np.array_equal(task.logits(ad, tok), base)


def _moe_cfg(**kw):
    import dataclasses

    cfg = get_arch("deepseek-v2-lite").reduced()
    return dataclasses.replace(cfg, num_experts=8, experts_per_token=2, d_model=64,
                               moe_d_ff=32, num_shared_experts=1, dtype="float32", **kw)


def test_held_shares_add_up_to_the_uncut_layer():
    full = _moe_cfg()
    params = moe_lib.moe_init(jax.random.PRNGKey(0), full)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 64))
    with jax.default_matmul_precision("highest"):
        y_full, _ = moe_lib.moe_apply(full, params, x, impl="ragged")
        y_dense, _ = moe_lib.moe_apply_dense(full, params, x)
        shared = y_full - moe_lib.moe_apply_ragged(full, params, x)[0]
        parts = []
        for off in (0, 4):
            cfg = _moe_cfg(expert_offset=off, experts_held=4)
            p = dict(params, **{n: params[n][off:off + 4] for n in ("wi", "wg", "wo")})
            parts.append(moe_lib.moe_apply(cfg, p, x, impl="ragged")[0])
    np.testing.assert_allclose(y_full - shared, y_dense, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(parts[0] + parts[1] - shared, y_full, rtol=1e-5, atol=1e-6)


def test_routing_stays_dropless_when_every_token_picks_one_expert():
    cfg = _moe_cfg(expert_offset=2, experts_held=4)
    params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    params["router"] = params["router"].at[:, 3].add(50.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (32, 64)))
    gates, idx, _ = moe_lib.route(cfg, params, x)
    assert bool(jnp.all(idx[:, 0] == 3))
    with jax.default_matmul_precision("highest"):
        y, _ = moe_lib.moe_apply_ragged(cfg, params, x)
        want = jnp.zeros_like(x)
        for j in range(2):
            for e in range(4):
                w = jnp.where(idx[:, j] == e + 2, gates[:, j], 0.0)[:, None]
                h = jax.nn.silu(x @ params["wg"][e]) * (x @ params["wi"][e])
                want = want + w * (h @ params["wo"][e])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)


def test_rows_past_the_held_pairs_may_hold_anything(monkeypatch):
    """On a TPU ``ragged_dot`` leaves the rows past its groups unwritten;
    whatever they hold must reach neither the output nor the gradient."""
    cfg = _moe_cfg(expert_offset=2, experts_held=4)
    params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))

    def run():
        moe_lib._held_ffn.cache_clear()
        f = lambda x: jnp.sum(moe_lib.moe_apply_ragged(cfg, params, x)[0] ** 2)
        return jax.value_and_grad(f)(x)

    want = run()
    orig = jax.lax.ragged_dot

    def garbage(lhs, rhs, group_sizes, **kw):
        out = orig(lhs, rhs, group_sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(group_sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", garbage)
    got = run()
    monkeypatch.undo()
    moe_lib._held_ffn.cache_clear()
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_array_equal(a, b)


def test_held_experts_batch_as_one_token_set():
    """The validator's vmap over candidates shares the experts' weights."""
    cfg = _moe_cfg(expert_offset=2, experts_held=4)
    params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    xs = jax.random.normal(jax.random.PRNGKey(2), (3, 10, 64))
    batched = jax.vmap(lambda x: moe_lib.moe_apply_ragged(cfg, params, x)[0])(xs)
    one = jnp.stack([moe_lib.moe_apply_ragged(cfg, params, x)[0] for x in xs])
    np.testing.assert_allclose(batched, one, rtol=1e-5, atol=1e-6)


def test_gating_is_softmax_then_top_k(ref, model):
    cfg = _moe_cfg(norm_topk_prob=False)
    params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
    gates, idx, _ = moe_lib.route(cfg, params, x)
    probs = np.asarray(jax.nn.softmax(x @ params["router"], axis=-1))
    want_idx = np.argsort(-probs, axis=-1)[:, :2]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want_idx, -1))
    np.testing.assert_allclose(gates, np.take_along_axis(probs, np.asarray(idx), -1),
                               rtol=1e-6)
    assert float(jnp.max(jnp.sum(gates, -1))) < 1.0
    dense = ref.gates(jax, jnp, model, x, params["router"], None)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(dense), np.asarray(idx), -1),
                               gates, rtol=1e-6)
    renorm, _, _ = moe_lib.route(_moe_cfg(norm_topk_prob=True), params, x)
    np.testing.assert_allclose(renorm, gates / jnp.sum(gates, -1, keepdims=True), rtol=1e-5)


def test_yarn_frequencies_and_scale_closed_form(ref):
    cfg = get_arch("deepseek-v2-lite")
    ys = cfg.rope_scaling
    dim, theta = cfg.rope_head_dim, cfg.rope_theta

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)), dim - 1)
    i = np.arange(dim // 2)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    base = theta ** (2.0 * i / dim)
    want = (1 / (40 * base)) * ramp + (1 / base) * (1 - ramp)
    np.testing.assert_allclose(yarn_freqs(dim, theta, ys), want, rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(dim, theta, {
        "original_max_position_embeddings": 4096, "beta_fast": 32, "beta_slow": 1,
        "factor": 40}), want, rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40.0, 0.707) == pytest.approx(m)
    assert float(softmax_scale(cfg)) == pytest.approx(192 ** -0.5 * m * m, rel=1e-6)


def test_second_episode_hits_the_stage_cache(ref, model, task):
    from repro.configs.base import DagFLConfig
    from repro.fl.nodes import SimNode  # noqa: F401  (the driver's node type)
    from repro.net import topology as topo

    class Node:
        behavior = "normal"

        def __init__(self, i):
            self.node_id, self.rng = i, np.random.default_rng(i)

        def epoch(self, steps, size):
            return {"tokens": self.rng.integers(0, 128, (steps, size, 8)).astype(np.int32)}

        def val_batch(self, size):
            return {"tokens": self.rng.integers(0, 128, (size, 8)).astype(np.int32)}

    dcfg = DagFLConfig(num_nodes=3, alpha=3, k=2, capacity=16, tx_size_bits=1e5)
    gval = {"tokens": np.zeros((2, 8), np.int32)}
    systems._stage_jits_cached.cache_clear()
    for ep in range(2):
        sim = systems.SimConfig(iterations=3, eval_every=3, minibatch=1,
                                steps_per_iter=1, val_size=1, seed=ep)
        systems.run_dagfl_gossip(task, [Node(i) for i in range(3)], dcfg, sim, gval,
                                 topology=topo.full(3), engine="events")
    info = systems._stage_jits_cached.cache_info()
    assert (info.misses, info.hits) == (1, 1)
    systems._stage_jits_cached.cache_clear()
