"""CPU tests of ``span_reduce``: host spans, device name scopes and idle time
attributed to spans, on traces built by hand.

    python -m pytest -q benchmarks/chip/tests/test_span_reduce.py
"""
from __future__ import annotations

import glob
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

import span_reduce as sr  # noqa: E402
import trace_reduce  # noqa: E402
import workload as wl  # noqa: E402

MS = 1_000_000
PLANE = "/device:TPU:0"
METRICS = ("idle_share.sim", "mfu.sim", "prepare_ms_per_iter.sim",
           "transport_ms_per_iter.sim", "net_dispatches_per_iter.sim",
           "gossip_winner_roofline")


def _trace(spans=()):
    """The device events of ``test_bench_chip._trace``, with host spans and
    the prepare op and the advance kernel tagged with scopes."""
    dev = {
        "ops": [("%while.4 = (s32[8]) while(...)", 10 * MS, 40 * MS),
                ("%fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(x)", 10 * MS, 20 * MS),
                ("%gossip_winner_pallas.3 = s32[8] custom-call()", 20 * MS, 40 * MS),
                ("%fusion.2 = f32[4]{0} fusion()", 60 * MS, 70 * MS), ("late", 95 * MS, 120 * MS)],
        "modules": [("jit_advance(17)", 10 * MS, 40 * MS),
                    ("jit_prepare(3)", 60 * MS, 70 * MS),
                    ("jit_prepare(3)", 95 * MS, 120 * MS)],
    }
    host = [("advance", 5 * MS, 6 * MS), ("prepare", 50 * MS, 51 * MS)]
    scopes = {PLANE: {("jit_prepare(3)", "%fusion.2 = f32[4]{0} fusion()"): "aggregate",
                      ("jit_prepare(3)", "late"): "train"}}
    return sr.SpanTrace({PLANE: dev}, host, (0, 100 * MS), spans, scopes)


def _span(name, a, b, it=None):
    return sr.Span(name, a * MS, b * MS, it)


def test_span_self_time_leaves_out_children_and_clips_to_the_window():
    spans = [_span("repro.fl.start", 0, 30, 0), _span("repro.net.read", 2, 7),
             _span("repro.fl.inputs", 8, 10), _span("repro.fl.prepare", 10, 25),
             _span("repro.fl.commit", 90, 130, 0)]
    times = sr.span_times(spans, 0, 100 * MS)
    assert times["repro.fl.start"]["total_s"] == pytest.approx(0.030)
    assert times["repro.fl.start"]["self_s"] == pytest.approx(0.030 - 0.005 - 0.002 - 0.015)
    assert times["repro.fl.prepare"]["self_s"] == pytest.approx(0.015)
    # the commit runs past the window's end: 10 ms of it count
    assert times["repro.fl.commit"] == {"total_s": pytest.approx(0.010),
                                        "self_s": pytest.approx(0.010), "count": 1}


def test_innermost_segments_nest_and_cut_a_child_at_its_parent():
    spans = [_span("a", 0, 10), _span("b", 2, 4), _span("c", 6, 12), _span("d", 20, 30)]
    assert sr.innermost(spans) == [(0, 2 * MS, "a"), (2 * MS, 4 * MS, "b"),
                                   (4 * MS, 6 * MS, "a"), (6 * MS, 10 * MS, "c"),
                                   (20 * MS, 30 * MS, "d")]


def test_idle_is_split_by_time_between_spans_and_the_unspanned_fallback():
    # device idle in [0, 10), [40, 60), [70, 95) of a 100 ms window
    spans = [_span("repro.fl.start", 0, 50, 1), _span("repro.fl.prepare", 45, 52),
             _span("repro.fl.commit", 75, 85, 1), _span("repro.net.wait.advance", 80, 84)]
    red = sr.reduce(_trace(spans))
    gaps = dict(red["idle_gaps"])
    # [0, 10): start; [40, 45): start; [45, 52): prepare (the span started
    # inside start but ends after it: cut at start's end, 50)
    assert gaps["repro.fl.start"] == pytest.approx(0.015)
    assert gaps["repro.fl.prepare"] == pytest.approx(0.005)
    # [50, 60): no span; the last dispatch before the gap at 40 was advance
    assert gaps["unspanned:advance"] == pytest.approx(0.010)
    # [70, 95): 5 ms unspanned, then commit 75-80 and 84-85, the wait 80-84,
    # then 10 ms unspanned; the last dispatch before 70 was prepare
    assert gaps["repro.fl.commit"] == pytest.approx(0.006)
    assert gaps["repro.net.wait.advance"] == pytest.approx(0.004)
    assert gaps["unspanned:prepare"] == pytest.approx(0.015)
    assert sum(gaps.values()) == pytest.approx(red["window_s"] - red["busy_s"])
    # inclusive idle per span, and idle inside any span
    assert red["idle_by_span"]["repro.fl.start"] == pytest.approx(0.020)
    assert red["idle_by_span"]["repro.fl.commit"] == pytest.approx(0.010)
    assert red["idle_by_span"]["repro.fl.prepare"] == pytest.approx(0.007)
    assert red["idle_spanned_s"] == pytest.approx(0.015 + 0.005 + 0.006 + 0.004)
    assert red["spans"]["repro.fl.start"]["count"] == 1


def test_scopes_sum_leaf_ops_by_stage_and_program():
    red = sr.reduce(_trace())
    # fusion.2 [60, 70) and late [95, 100) in prepare
    assert red["scopes"] == {"aggregate": pytest.approx(0.010), "train": pytest.approx(0.005)}
    assert red["scopes_by_program"]["advance"] == {"": pytest.approx(0.030)}
    assert red["scopes_by_program"]["prepare"]["aggregate"] == pytest.approx(0.010)
    assert red["scopes_inherited"] == {}


class _Event:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns = name, start, end - start


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message: int values as varints, str/bytes length-delimited,
    float as a fixed64 double."""
    import struct

    out = b""
    for num, value in fields:
        if isinstance(value, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", value)
        elif isinstance(value, int):
            out += _varint(num << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(num << 3 | 2) + _varint(len(value)) + value
    return out


def _xspace():
    """A device plane whose event metadata names two programs' ops, one
    scope by string and one by reference, and a host plane to ignore."""
    stat_names = {1: "program_id", 2: "tf_op", 3: "flops", 4: "jit(other)/dagfl/commit/x"}
    stat_md = [(5, _msg((1, k), (2, _msg((1, k), (2, v))))) for k, v in stat_names.items()]

    def event(i, name, *stats):
        return (4, _msg((1, i), (2, _msg((1, i), (2, name), *[(5, _msg(*st)) for st in stats]))))

    device = _msg(
        (1, 7), (2, PLANE), (3, b"\x08\x01\x12\x03ops"),   # a line, skipped
        event(1, "%dot.1 = f32[8]", ((1, 1), (3, 11)),
              ((1, 2), (5, "jit(prepare)/dagfl/train/while/body/dot_general")),
              ((1, 3), (2, 2.0))),
        event(2, "%dot.1 = f32[8]", ((1, 1), (3, 22)), ((1, 2), (7, 4))),
        event(3, "%copy.2 = f32[8]", ((1, 1), (3, 11))),
        event(4, "jit_prepare(11)"),                           # a module: no program id
        *stat_md)
    host = _msg((2, "/host:CPU"), event(1, "%dot.1 = f32[8]", ((1, 1), (3, 11))), *stat_md)
    return _msg((1, device), (1, host))


def test_op_scopes_read_the_event_metadata_once_per_program_and_op():
    tables = sr.op_scopes(memoryview(_xspace()))
    assert tables == {PLANE: {("11", "%dot.1 = f32[8]"): "train",
                              ("22", "%dot.1 = f32[8]"): "commit",
                              ("11", "%copy.2 = f32[8]"): ""}}


def test_device_plane_maps_each_op_to_its_programs_scope():
    table = sr.op_scopes(memoryview(_xspace()))[PLANE]
    ops = [_Event("%dot.1 = f32[8]", 10 * i, 10 * i + 5) for i in range(50)]
    ops.append(_Event("%dot.1 = f32[8]", 600, 605))     # the same text in another program
    ops.append(_Event("%copy.2 = f32[8]", 606, 607))
    ops.append(_Event("%stray = f32[8]", 700, 701))     # in no module
    mods = [_Event("jit_prepare(11)", 0, 500), _Event("jit_other(22)", 600, 610)]
    lines, scopes = sr.device_plane({"XLA Ops": ops, "XLA Modules": mods}, table)
    assert scopes == {("jit_prepare(11)", "%dot.1 = f32[8]"): "train",
                      ("jit_other(22)", "%dot.1 = f32[8]"): "commit",
                      ("jit_other(22)", "%copy.2 = f32[8]"): ""}
    assert len(lines["ops"]) == 53 and len(lines["modules"]) == 2
    assert lines["ops"][0] == ("%dot.1 = f32[8]", 0, 5)


def test_inserted_ops_take_the_stage_of_the_op_that_needs_them():
    mods = [("jit_prepare(1)", 0, 100), ("jit_prepare(1)", 200, 300)]
    table = {("jit_prepare(1)", "dot"): "aggregate", ("jit_prepare(1)", "conv"): "train"}
    leaves = [("copy", 0, 10), ("dot", 10, 20), ("conv", 20, 30), ("tail", 30, 40),
              ("copy", 200, 230), ("other", 400, 410)]
    staged = sr._staged(leaves, mods, table)
    # the first copy precedes the dot; the tail follows the conv; the second
    # execution has no scoped op to lend a stage; the last op is in no module
    assert [row[:3] for row in staged] == [
        ("prepare", "aggregate", True), ("prepare", "aggregate", False),
        ("prepare", "train", False), ("prepare", "train", True),
        ("prepare", "", False), ("", "", False)]
    assert [row[3] for row in staged] == pytest.approx([1e-8] * 4 + [3e-8, 1e-8])


def test_scope_of_takes_the_component_after_the_first_dagfl():
    assert sr.scope_of("jit(prepare)/dagfl/aggregate/dot") == "aggregate"
    assert sr.scope_of("jit(prepare)/dagfl/train/while/body/dagfl/x/add") == "train"
    assert sr.scope_of("jit(p)/dagfl/commit:") == "commit"
    assert sr.scope_of("jit(f)/vmap(dagfl/select)/y") == ""
    assert sr.scope_of("jit(f)/notdagfl/train") == ""
    assert sr.scope_of("") == ""


def _cell():
    config = {"dagfl": {"num_nodes": 100, "capacity": 192}}
    return types.SimpleNamespace(config=config)


@pytest.mark.parametrize("metric", METRICS)
def test_existing_metrics_read_the_same_from_the_old_and_new_reduce(metric):
    spans = [_span("repro.fl.start", 0, 50, 1), _span("repro.fl.commit", 75, 85, 1)]
    trace = _trace(spans)
    old, new = trace_reduce.reduce(trace), sr.reduce(trace)
    for key in old:
        if key != "idle_gaps":
            assert new[key] == old[key], key
    mod = wl.load_module(os.path.join(BENCH, "metrics", metric + ".py"),
                         "m_" + metric.replace(".", "_"))
    base = {"cell": _cell(), "committed": 3, "device_calls": 6,
            "untraced_committed": 100, "untraced_wall_s": 5.0,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "flops": {"train": 1e9}}
    assert mod.read(dict(base, trace=new)) == mod.read(dict(base, trace=old))
    assert mod.read(dict(base, trace=new)) is not None


def test_load_keeps_spans_with_their_iteration(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x).sum())
    x = jnp.ones((8,))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            with jax.profiler.TraceAnnotation("repro.fl.start", iteration=4):
                with jax.profiler.TraceAnnotation("repro.fl.prepare"):
                    f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("other"):
                pass
    (path,) = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)
    trace = sr.load(path)
    assert trace.window is not None
    assert [(s.name, s.iteration) for s in sorted(trace.spans, key=lambda s: s.start)] == [
        ("repro.fl.start", 4), ("repro.fl.prepare", None)]
    start, prep = sorted(trace.spans, key=lambda s: s.start)
    assert start.start <= prep.start < prep.end <= start.end
    assert any(name for name, _, _ in trace.host)
