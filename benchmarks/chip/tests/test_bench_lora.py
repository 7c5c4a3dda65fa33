"""CPU tests of the DeepSeek-V2-Lite LoRA cell: the pieces a chip run cannot check.

    python -m pytest -q benchmarks/chip/tests/test_bench_lora.py

The cell loads by name with its configuration checked against the
published one, the expert product's counts and the model FLOPs by hand,
and, at a tiny size run end to end through the harness, that ``correct``
comes out true for the program as it is and false for a training step that
returns its input and for a dropped routed pair.
"""
from __future__ import annotations

import copy
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import workload as wl  # noqa: E402

CELL = "dsv2lite-lora.ideal-nobank"

TINY_ARCH = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
             "num_hidden_layers": 2, "num_attention_heads": 2, "num_key_value_heads": 2,
             "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "n_routed_experts": 4, "n_shared_experts": 1,
             "num_experts_per_tok": 2, "vocab_size": 128}


def _load():
    return wl.load_cell(ROOT, CELL)


def test_configuration_is_the_published_one_but_for_the_cut():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = {c["name"]: c for c in bench["configs"]}["dsv2lite-lora"]
    cell = _load()
    cfg = cell.config
    published = {"hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
                 "moe_intermediate_size": 1408, "num_attention_heads": 16,
                 "num_experts_per_tok": 6, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "n_shared_experts": 2, "norm_topk_prob": False,
                 "q_lora_rank": None, "rms_norm_eps": 1e-6, "first_k_dense_replace": 1}
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["rope_scaling"]["factor"] == 40 and cfg["rope_scaling"]["mscale"] == 0.707
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 16, 25600)
    assert set(conf["reduced"]) == set(cfg["reduced"])
    assert cfg["model"]["router_experts"] == 64
    # the model's copy of the architecture is the file's own
    assert all(cfg[k] == v for k, v in cfg["model"]["arch"].items())
    assert {m["name"] for m in cell.per_layer} >= {"moe_experts_roofline",
                                                   "moe_experts_ms_per_iter.lora"}


def test_payload_and_base_sizes():
    cell = _load()
    model = cell.config["model"]
    # rank-16 adapters on q, kv_a, kv_b and o of five layers
    per = 16 * ((2048 + 3072) + (2048 + 576) + (512 + 4096) + (2048 + 2048))
    assert cell.task.param_count(model) == 5 * per == 1_315_840
    assert cell.config["dagfl"]["tx_size_bits"] == 32 * 1_315_840
    assert cell.task.base_param_count(model) == pytest.approx(864.4e6, rel=1e-3)


def test_expert_counts_and_flops_by_hand():
    cell = _load()
    model = cell.config["model"]
    # 1024 tokens of 6 pairs each; a quarter of them land on the 16 held experts
    assert cell.task.expert_pairs(6144, model) == 1536
    assert cell.task.expert_call_flops(6144, model) == 2 * 1536 * 2048 * 1408
    assert cell.task.expert_call_bytes(6144, model) == 4 * (16 * 2048 * 1408
                                                            + 1536 * (2048 + 1408))
    macs = cell.task.forward_macs(model, 512)
    assert macs["experts"] == 4 * 1.5 * 3 * 2048 * 1408
    assert macs["head"] == 2048 * 25600
    fwd = 2 * sum(macs.values())
    assert 0.6e9 < fwd < 0.7e9                      # about 0.64 GFLOP a token
    f = cell.task.flops(cell.config, 25, 40)
    assert f["train"] == 4 * 2 * 512 * (2 * fwd + 2 * macs["lora"])
    assert f["validate"] == 6 * 2 * 512 * fwd
    assert 8e12 < sum(f.values()) < 11e12           # about 9 TFLOP an iteration


def test_roofline_reader_reads_the_ragged_products():
    cell = _load()
    peaks = harness.load_peaks("TPU v5 lite")
    roof = wl.load_module(os.path.join(BENCH, "metrics", "moe_experts_roofline.py"), "m_mr")
    ms = wl.load_module(os.path.join(BENCH, "metrics", "moe_experts_ms_per_iter.lora.py"),
                        "m_mm")
    model = cell.config["model"]
    need = max(cell.task.expert_call_bytes(6144, model) / peaks["hbm_bytes_per_s"],
               cell.task.expert_call_flops(6144, model) / peaks["flops_per_s"])
    ops = {("prepare", "ragged-dot-none.3 = f32[6144,1408]"): (4 * need, 2),
           ("prepare", "ragged-dot-none.9 = f32[6144,2048]"): (2 * need, 1),
           ("prepare", "ragged-dot-metadata.1 = (s32[17], s32[18])"): (1.0, 3),
           ("prepare", "fusion.2 = f32[6144,2048]"): (1.0, 1)}
    ctx = {"cell": cell, "peaks": peaks, "committed": 3,
           "trace": {"ops_by_program": ops}}
    assert roof.read(ctx) == pytest.approx(100 * 3 * need / (6 * need))
    assert ms.read(ctx) == pytest.approx(1e3 * 6 * need / 3)
    ctx["trace"]["ops_by_program"] = {("prepare", "fusion.2 = f32[6144,2048]"): (1.0, 1)}
    assert roof.read(ctx) is None and ms.read(ctx) is None


# ---------------------------------------------------------------------------
# the whole run at a tiny size: sound, and planted faults
# ---------------------------------------------------------------------------


def tiny_cell():
    cell = _load()
    cfg = copy.deepcopy(cell.config)
    cfg.update(TINY_ARCH)
    cfg["model"]["arch"].update(TINY_ARCH)
    cfg["model"].update(router_experts=8, expert_offset=2)
    cfg["model"]["lora"].update(rank=4, alpha=8)
    cfg["dagfl"].update(num_nodes=6, capacity=32)
    cfg["data"].update(seq_len=16, seqs_per_node=8, global_val=4)
    cfg["iterations"] = 10
    cell.config = cfg
    cell.traffic = dict(cell.traffic, eval_every=4)
    return cell


class _NoCompiles:
    def reset(self):
        return [], 0.0


def run_tiny(cell, seed=5):
    import jax

    args = types.SimpleNamespace(seed=seed, seconds=0.0, trace=0)
    return harness.run_cell(cell, args, jax, _NoCompiles(), 0.0, None,
                            lambda msg: None)


@pytest.fixture
def program():
    """The program's modules, with their caches (and JAX's traces) cleared
    round the test so a planted fault cannot leak into another."""
    import jax

    from repro.fl import systems, tasks
    from repro.models import moe

    def clear():
        systems._stage_jits_cached.cache_clear()
        moe._held_ffn.cache_clear()
        jax.clear_caches()

    clear()
    yield types.SimpleNamespace(systems=systems, tasks=tasks, moe=moe, clear=clear)
    clear()


def test_sound_program_is_correct(program):
    res = run_tiny(tiny_cell())
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] == 10
    assert res["compared"]["update_gap"]["value"] < 1e-5


def test_fault_training_step_returns_state_unchanged(monkeypatch, program):
    monkeypatch.setattr(program.systems, "make_epoch_train",
                        lambda task: (lambda params, batch, key: (params, {"loss": 0.0})))
    res = run_tiny(tiny_cell())
    assert not res["correct"]
    assert res["compared"]["update_gap"]["value"] > res["compared"]["update_gap"]["limit"]


def test_fault_dropped_routed_pair(monkeypatch, program):
    """The first (token, expert) pair routed to a held expert is left out."""
    import jax.numpy as jnp

    orig = program.moe._held_ffn_plain

    def drop_one(cfg, x, top_idx, gates, w):
        local = top_idx - cfg.expert_offset
        held = (local >= 0) & (local < cfg.resolved_experts_held())
        first = jnp.argmax(held.reshape(-1))
        g = gates.reshape(-1).at[first].set(0.0).reshape(gates.shape)
        return orig(cfg, x, top_idx, g, w)

    monkeypatch.setattr(program.moe, "_held_ffn_plain", drop_one)
    res = run_tiny(tiny_cell())
    assert not res["correct"], res["compared"]
