"""From a JAX profiler trace to the numbers the per-layer metrics read.

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and
keeps two kinds of events, each as ``(name, start_ns, end_ns)``:

* device events: the ``XLA Ops`` line of every ``/device:`` plane (one
  event per operation the device ran), and that plane's ``XLA Modules``
  line (one event per program execution, named after the jitted
  function);
* host events: the dispatches on the host plane's Python thread
  (``PjitFunction(<name>)``, one per call of a jitted function or eager
  operation), kept under the function's name.

``reduce(trace)`` clips them to the window the harness marked with a host
annotation (``WINDOW``) and gives:
the union of device-op intervals (busy time), the window, device time per
program (summed over the intervals of its module executions) and per
operation (leaf operations only: a ``while`` that contains others is
left to them), each operation's time and count by the program it ran in
(the module execution that contains its start), and the longest idle gaps
labelled by the last host dispatch that started before each gap.
Pure Python over plain tuples, so the tests build traces by hand.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)


WINDOW = "bench_window"     # the host annotation the harness puts round the window


class Trace:
    def __init__(self, devices: Dict[str, Dict[str, List[Event]]],
                 host: List[Event], window: Optional[Tuple[int, int]] = None):
        self.devices = devices        # plane -> {"ops": [...], "modules": [...]}
        self.host = host
        self.window = window          # (start_ns, end_ns) of the WINDOW annotation


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    lines[key].append((e.name, s, s + int(e.duration_ns)))
            devices[plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    if e.name == WINDOW:
                        window = (s, s + int(e.duration_ns))
                    m = _DISPATCH.match(e.name)
                    if m:
                        host.append((m.group(1), s, s + int(e.duration_ns)))
    return Trace(devices, host, window)


def _clip(events: List[Event], t0: int, t1: int) -> List[Event]:
    out = []
    for name, s, e in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((name, s, e))
    return out


def union_intervals(events: List[Event]) -> List[Tuple[int, int]]:
    """Merged, sorted [start, end) intervals covered by any event."""
    spans = sorted((s, e) for _, s, e in events if e > s)
    merged: List[List[int]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


_MODULE_SUFFIX = re.compile(r"(\(\d+\))$")
_DISPATCH = re.compile(r"^PjitFunction\((.*)\)$")


def op_name(hlo: str, width: int = 100) -> str:
    """``%fusion.3 = f32[8,128]{...} fusion(...)`` -> ``fusion.3 = f32[8,128]``:
    the instruction's name and result type, cut to ``width`` letters."""
    name, _, rest = hlo.lstrip("%").partition(" = ")
    out = f"{name} = {rest.split('{', 1)[0].split(' ', 1)[0]}" if rest else name
    return out[:width]


def leaf_ops(events: List[Event]) -> List[Event]:
    """The events that contain no other event (a ``while`` or ``conditional``
    op is listed beside the ops it runs; keep those ops, not the container)."""
    ordered = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt[1] >= e[2] or nxt[2] > e[2]]


def program_name(module_event_name: str) -> str:
    """``jit_prepare(1234)`` -> ``prepare``: the jitted function's name."""
    name = _MODULE_SUFFIX.sub("", module_event_name)
    return name[4:] if name.startswith("jit_") else name


def reduce(trace: Trace, top: int = 10) -> dict:
    """Busy time, per-program and per-op device time, and idle gaps.

    The window is the trace's ``WINDOW`` annotation. Times are in seconds;
    device quantities are averaged over the device planes that ran
    anything in the window.
    """
    if trace.window is None:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    t0_ns, t1_ns = trace.window
    window = (t1_ns - t0_ns) * 1e-9
    per_dev_busy, programs, ops = [], defaultdict(float), defaultdict(float)
    by_program = defaultdict(lambda: [0.0, 0])
    gaps = []
    host = sorted(_clip(trace.host, t0_ns, t1_ns), key=lambda e: e[1])
    host_starts = [s for _, s, _ in host]
    active, all_ops = 0, []
    for lines in trace.devices.values():
        dev_ops = _clip(lines["ops"], t0_ns, t1_ns)
        if not dev_ops:
            continue
        active += 1
        all_ops += dev_ops
        spans = union_intervals(dev_ops)
        per_dev_busy.append(sum(e - s for s, e in spans) * 1e-9)
        modules = sorted(_clip(lines["modules"], t0_ns, t1_ns), key=lambda m: m[1])
        starts = [s for _, s, _ in modules]
        for name, s, e in modules:
            programs[program_name(name)] += (e - s) * 1e-9
        for name, s, e in leaf_ops(dev_ops):
            name = op_name(name)
            ops[name] += (e - s) * 1e-9
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < modules[i][2]
            slot = by_program[(program_name(modules[i][0]) if inside else "", name)]
            slot[0] += (e - s) * 1e-9
            slot[1] += 1
        edges = [t0_ns] + [x for span in spans for x in span] + [t1_ns]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    if active and max(e for _, _, e in all_ops) < t1_ns - 0.25 * (t1_ns - t0_ns):
        raise ValueError("the device trace stops well before the window ends: "
                         "the profiler dropped events")
    if not active:
        return {"busy_s": 0.0, "window_s": window, "programs": {}, "ops": {},
                "ops_by_program": {}, "device_ops": [], "idle_gaps": []}
    scale = 1.0 / active
    labelled = defaultdict(float)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])
    for a, b in longest:
        i = bisect.bisect_right(host_starts, a) - 1
        label = host[i][0] if i >= 0 else "before the first dispatch"
        labelled[label] += (b - a) * 1e-9 * scale
    return {
        "busy_s": sum(per_dev_busy) * scale,
        "window_s": window,
        "programs": {k: v * scale for k, v in programs.items()},
        "ops": {k: v * scale for k, v in ops.items()},
        "ops_by_program": {k: (v[0] * scale, v[1] * scale)
                           for k, v in by_program.items()},
        "device_ops": sorted(((f"{prog}: {op}" if prog else op, v[0] * scale)
                              for (prog, op), v in by_program.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(labelled.items(), key=lambda kv: -kv[1])[:top],
    }

