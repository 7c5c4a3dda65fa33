"""CPU tests of the chip benchmark: the pieces a chip run cannot check.

    python -m pytest -q benchmarks/chip/tests

The trace reduction, the FLOP counts, the roofline arithmetic and the peak
table, loading cells by name, the refusal to run without a TPU, and, on a
tiny configuration run end to end through the harness, that ``correct``
comes out true for the program as it is and false for the control and for
each fault planted in the timed path.
"""
from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import harness  # noqa: E402
import trace_reduce  # noqa: E402
import workload as wl  # noqa: E402

CELLS = ("dagfl-cnn.ideal-nobank", "dagfl-lstm.ideal-nobank")


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def _trace():
    ms = 1_000_000
    dev = {
        "ops": [("%while.4 = (s32[8]) while(...)", 10 * ms, 40 * ms),
                ("%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(x)", 10 * ms, 20 * ms),
                ("%gossip_winner_pallas.3 = s32[8] custom-call()", 20 * ms, 40 * ms),
                ("%fusion.2 = f32[4]{0} fusion()", 60 * ms, 70 * ms), ("late", 95 * ms, 120 * ms)],
        "modules": [("jit_advance(17)", 10 * ms, 40 * ms),
                    ("jit_prepare(3)", 60 * ms, 70 * ms),
                    ("jit_prepare(3)", 95 * ms, 120 * ms)],
    }
    host = [("advance", 5 * ms, 6 * ms), ("prepare", 50 * ms, 51 * ms)]
    return trace_reduce.Trace({"/device:TPU:0": dev}, host, (0, 100 * ms))


def test_trace_busy_union_idle_and_programs():
    red = trace_reduce.reduce(_trace())
    # ops cover [10, 40) and [60, 70) and [95, 100) of a 100 ms window
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.045)
    assert red["programs"]["advance"] == pytest.approx(0.030)
    assert red["programs"]["prepare"] == pytest.approx(0.015)
    secs, calls = red["ops_by_program"][("advance", "gossip_winner_pallas.3 = s32[8]")]
    assert secs == pytest.approx(0.020) and calls == 1
    name, secs = red["device_ops"][0]
    assert name == "advance: gossip_winner_pallas.3 = s32[8]" and secs == pytest.approx(0.020)
    # the while op is left to the ops it contains
    assert set(red["ops"]) == {"fusion.1 = f32[8,128]", "gossip_winner_pallas.3 = s32[8]",
                               "fusion.2 = f32[4]", "late"}
    # idle gaps go to the last dispatch that started before them: [0, 10)
    # to none, [40, 60) to the advance dispatch, [70, 95) to the prepare one
    gaps = dict(red["idle_gaps"])
    assert gaps["before the first dispatch"] == pytest.approx(0.010)
    assert gaps["advance"] == pytest.approx(0.020)
    assert gaps["prepare"] == pytest.approx(0.025)
    ctx = {"trace": red}
    idle = wl.load_module(os.path.join(BENCH, "metrics", "idle_share.sim.py"), "m_idle")
    assert idle.read(ctx) == pytest.approx(55.0)


def test_trace_union_merges_overlaps():
    spans = trace_reduce.union_intervals([("a", 0, 10), ("b", 5, 20), ("c", 30, 31),
                                          ("d", 20, 25)])
    assert spans == [(0, 25), (30, 31)]
    assert trace_reduce.program_name("jit_advance(12)") == "advance"


def test_trace_without_window_is_an_error():
    tr = _trace()
    tr.window = None
    with pytest.raises(ValueError):
        trace_reduce.reduce(tr)


# ---------------------------------------------------------------------------
# FLOPs, roofline, peaks
# ---------------------------------------------------------------------------


def _load(cell_name):
    return wl.load_cell(ROOT, cell_name)


def test_cnn_flops_by_hand():
    cell = _load(CELLS[0])
    macs = cell.task.forward_macs(cell.config["model"])
    # 28x28x(5x5x1x32), 14x14x(5x5x32x64), 3136x512, 512x10
    assert macs == {"conv1": 627_200, "conv2": 10_035_200, "fc": 1_605_632, "out": 5_120}
    assert cell.task.param_count(cell.config["model"]) == 1_663_370
    f = cell.task.flops(cell.config, 25, 100)
    fwd = 2 * 12_273_152
    assert f["train"] == 128 * (3 * fwd - 2 * 627_200)
    assert f["validate"] == 6 * 64 * fwd
    assert f["agent"] == pytest.approx(256 * (6 * 4 + 1) * fwd / 100)


def test_lstm_flops_by_hand():
    cell = _load(CELLS[1])
    macs = cell.task.step_macs(cell.config["model"])
    # (8 + 256) x 1024, (256 + 256) x 1024, 256 x 90 per character
    assert macs == {"lstm0": 270_336, "lstm1": 524_288, "out": 23_040}
    line = 2 * 817_664 * 80
    f = cell.task.flops(cell.config, 25, 100)
    assert f["train"] == 5 * 4 * 32 * 3 * line
    assert f["validate"] == 6 * 64 * line
    # 90x8 embedding, 256x90+90 output, (264+1)x1024 and (512+1)x1024 gates
    assert cell.task.param_count(cell.config["model"]) == 720 + 23_130 + 271_360 + 525_312


def test_roofline_need_and_peaks():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["flops_per_s"] == 197e12 and peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.load_peaks("_source")
    roof = wl.load_module(os.path.join(BENCH, "metrics", "gossip_winner_roofline.py"),
                          "m_roof")
    # R=100, cap=192: 4 * (3*100*192 + 100*100 + 2*100*192) bytes over 819 GB/s
    need = roof.need_s(100, 100, 192, peaks)
    assert need == pytest.approx(4 * (57_600 + 10_000 + 38_400) / 819e9)
    cell = _load(CELLS[0])
    ctx = {"cell": cell, "peaks": peaks, "trace": {"ops_by_program": {
        ("advance", "gossip_winner_pallas.2 = s32[100,1,192]"): (2 * need * 10, 2),
        ("merge_all", "gossip_winner_pallas.5 = s32[1,1,192]"): (0.0, 1)}}}
    one = roof.need_s(1, 100, 192, peaks)
    assert roof.read(ctx) == pytest.approx(100 * (2 * need + one) / (20 * need))
    ctx["trace"]["ops_by_program"][("prepare", "gossip_winner_pallas.9")] = (1.0, 1)
    assert roof.read(ctx) is None


def test_mfu_reads_the_untraced_episodes_only():
    mfu = wl.load_module(os.path.join(BENCH, "metrics", "mfu.sim.py"), "m_mfu")
    ctx = {"flops": {"train": 2e9, "validate": 1e9}, "peaks": {"flops_per_s": 1e12},
           "committed": 100, "untraced_committed": 300, "untraced_wall_s": 10.0}
    # 3 GFLOP per iteration at 30 iterations/s of a 1 TFLOP/s peak
    assert mfu.read(ctx) == pytest.approx(9.0)
    ctx.update(untraced_committed=0, untraced_wall_s=0.0)
    assert mfu.read(ctx) is None


# ---------------------------------------------------------------------------
# loading by name
# ---------------------------------------------------------------------------


def test_cells_load_by_name_with_every_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = wl.load_cell(ROOT, w["name"])
        assert cell.traffic_name == w["traffic"] and cell.config_name == w["config"]
        assert set(cell.config["correct_limits"]) >= {"ledger_mismatch", "update_gap"}
        for m in cell.per_layer:
            mod = wl.load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                                 "m_" + m["name"].replace(".", "_"))
            assert callable(mod.read)
    with pytest.raises(KeyError):
        wl.load_cell(ROOT, "no-such.cell")


def test_seeds_are_fixed_and_any_size():
    big = 2**31 + 12345
    assert wl.episode_seed(big, 3) == wl.episode_seed(big, 3)
    assert wl.episode_seed(big, 3) != wl.episode_seed(big, 4)
    assert 0 <= wl.episode_seed(2**40, 0) < 2**20
    with pytest.raises(ValueError):
        wl.episode_seed(-1, 0)


def test_link_classes_dealt_in_equal_shares():
    table = {"a": 1e6, "b": 1e7, "c": 1e8}
    bw = wl.link_bandwidth({"link_classes": ["a", "b", "c"]}, 10,
                           np.random.SeedSequence(1), table)
    assert np.array_equal(bw, bw.T) and np.all(np.diag(bw) == 0)
    upper = bw[np.triu_indices(10, 1)]
    assert sorted(np.unique(upper, return_counts=True)[1]) == [15, 15, 15]


# ---------------------------------------------------------------------------
# no chip, no result
# ---------------------------------------------------------------------------


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd,
                          capture_output=True, text=True, env=env, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0"], ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0"], tmp_path)
    assert out.returncode != 0 and out.stdout.strip() == ""


# ---------------------------------------------------------------------------
# the whole run at a tiny size: sound, the control, and planted faults
# ---------------------------------------------------------------------------


def tiny_cell(task="cnn", traffic=None):
    cell = _load(CELLS[0] if task == "cnn" else CELLS[1])
    if traffic is not None:
        with open(os.path.join(BENCH, "traffic", traffic + ".json")) as f:
            cell.traffic = json.load(f)
    cfg = copy.deepcopy(cell.config)
    cfg["dagfl"].update(num_nodes=6, capacity=32)
    if task == "cnn":
        cfg["model"].update(image_size=16, channels=[4, 8], fc_units=32)
        cfg["data"].update(shard_size=10, uniform_per_node=10, global_val=32)
    else:
        cfg["model"].update(hidden=16, embed_dim=4)
        cfg["data"].update(lines_per_node=16, line_len=12, roles=4,
                           global_val_roles=2, global_val_lines=8)
    cfg["sim"].update(minibatch=4, val_size=8)
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
    """The program's modules, with their caches cleared after the test so a
    planted fault cannot leak into another."""
    from repro.fl import systems, tasks

    yield types.SimpleNamespace(systems=systems, tasks=tasks)
    systems._stage_jits_cached.cache_clear()


@pytest.mark.parametrize("task,traffic", [("cnn", None), ("lstm", None),
                                          ("cnn", "tablei-bank")])
def test_sound_program_is_correct(task, traffic, program):
    res = run_tiny(tiny_cell(task, traffic))
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"
    assert res["failed"] == 0 and res["attempted"] == 10


def _plant(monkeypatch, program, name, fn):
    program.systems._stage_jits_cached.cache_clear()
    monkeypatch.setattr(program.systems, name, fn)


def test_control_bf16_model_is_not_correct(monkeypatch, program):
    import calibrate
    import jax

    cell = tiny_cell()
    orig = cell.task.program_task
    monkeypatch.setattr(cell.task, "program_task", lambda mod, model: calibrate.bf16_task(
        cell, jax, orig(mod, model)))
    program.systems._stage_jits_cached.cache_clear()
    res = run_tiny(cell)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("control", [None, "bf16"])
def test_calibrate_reports_the_benchmarks_correct(control, program):
    import calibrate
    import jax

    cell = tiny_cell()
    program.systems._stage_jits_cached.cache_clear()
    line = calibrate.reading(cell, 5, control, jax)
    assert line["correct"] is (control is None), line["compared"]
    assert set(line["compared"]) == set(cell.config["correct_limits"])


def test_fault_training_step_returns_state_unchanged(monkeypatch, program):
    _plant(monkeypatch, program, "make_epoch_train",
           lambda task: (lambda params, batch, key: (params, {"loss": 0.0})))
    res = run_tiny(tiny_cell())
    assert not res["correct"]
    assert res["compared"]["update_gap"]["value"] > res["compared"]["update_gap"]["limit"]


def test_fault_half_batch_mean_over_rest(monkeypatch, program):
    orig = program.tasks.make_epoch_train

    def half(task):
        train = orig(task)

        def run(params, batch, key):
            n = next(iter(batch.values())).shape[1] // 2
            return train(params, {k: v[:, :n] for k, v in batch.items()}, key)

        return run

    _plant(monkeypatch, program, "make_epoch_train", half)
    res = run_tiny(tiny_cell())
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("traffic", [None, "tablei-bank"])
def test_fault_transport_returns_state_unchanged(traffic, monkeypatch, program):
    from repro.net import events

    name = "_advance_events_bank_jit" if traffic else "_advance_events_jit"
    orig = getattr(events, name)

    def frozen(*a, **k):
        fn = orig(*a, **k)

        def run(dags, *rest):
            out = fn(dags, *rest)
            return (dags,) + tuple(out[1:])

        return run

    monkeypatch.setattr(events, name, frozen)
    res = run_tiny(tiny_cell(traffic=traffic))
    assert not res["correct"]
    assert res["compared"]["ledger_mismatch"]["value"] > 0


def test_fault_answer_altered_where_produced(monkeypatch, program):
    import jax

    orig = program.systems._gossip_commit

    def altered(dag, bank, node_id, t, prepared, seq):
        bumped = jax.tree_util.tree_map(lambda p: p * 1.01, prepared.new_params)
        return orig(dag, bank, node_id, t, prepared._replace(new_params=bumped), seq)

    monkeypatch.setattr(program.systems, "_gossip_commit", altered)
    program.systems._jit_of.cache_clear()
    res = run_tiny(tiny_cell())
    assert not res["correct"], res["compared"]
