"""Host spans, device name scopes and idle time attributed to spans, from a
JAX profiler trace.

The program puts its host loop on the profiler's clock with
``jax.profiler.TraceAnnotation`` spans named ``repro.*`` (``repro.fl.start``
and ``repro.fl.commit`` carry an ``iteration`` stat), and tags the stages
of its prepare and commit programs with ``jax.named_scope("dagfl/<stage>")``.
This module reads both, on top of ``trace_reduce``:

* ``load(path)`` keeps what ``trace_reduce.load`` keeps, and also the
  ``repro.*`` events of the host's Python thread as ``Span`` tuples and,
  for each device operation, its scope: the first ``dagfl/<stage>``
  component of the name-scope path the profiler records with it. The path
  sits in the event metadata, one entry per distinct (program, operation),
  and is read once from there (``op_scopes``), since a traced episode
  runs millions of operations.
* ``reduce(trace)`` is ``trace_reduce.reduce`` with the same keys and
  values, except ``idle_gaps``, whose labels now name the innermost
  ``repro.*`` span open during each part of a gap, split by time; a part
  that no span covers keeps the old label (the last dispatch before the
  gap), prefixed ``unspanned:``. It adds:

  ``spans``             name -> total and self seconds (clipped to the
                        window) and count;
  ``scopes``            stage -> device seconds of leaf operations under
                        ``dagfl/<stage>``;
  ``scopes_by_program`` program -> {stage, or "" for none: seconds};
  ``scopes_inherited``  program -> seconds of operations the compiler
                        inserted without a scope, counted under the stage
                        of the operation that needs them (``_staged``);
  ``idle_by_span``      name -> device idle seconds inside the span;
  ``idle_spanned_s``    device idle seconds inside any span (the
                        labels of ``idle_gaps`` without ``unspanned:``).

Device quantities are averaged over the device planes that ran anything in
the window, as in ``trace_reduce``. Pure Python over plain tuples, so the
tests build traces by hand.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

import trace_reduce as tr

SPAN_PREFIX = "repro."
SCOPE = "dagfl"
UNSPANNED = "unspanned:"


class Span(NamedTuple):
    name: str
    start: int            # ns
    end: int              # ns
    iteration: Optional[int] = None


class SpanTrace(tr.Trace):
    def __init__(self, devices, host, window=None, spans=(), scopes=None):
        super().__init__(devices, host, window)
        self.spans = list(spans)            # host spans, ``Span``
        # plane -> {(module event name, op event name): stage}
        self.scopes = scopes or {}


def scope_of(path: str) -> str:
    """The component after the first ``dagfl`` of a name-scope path such as
    ``jit(prepare)/dagfl/train/while/body/dot_general`` (the profiler may
    end a path with ``:``), or ""."""
    parts = path.split("/")
    for scope, stage in zip(parts, parts[1:]):
        if scope == SCOPE:
            return stage.rstrip(":")
    return ""


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start: int, end: int):
    """(field number, value) of the protobuf message in ``buf[start:end]``;
    a length-delimited value is its ``(start, end)``."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = int.from_bytes(buf[i:i + n], "little"), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_scopes(buf) -> Dict[str, Dict[Tuple[str, str], str]]:
    """Per device plane, the stage of each distinct operation, keyed by
    (program id, HLO text): read once from the XSpace's event metadata.

    The profiler keeps an operation's name-scope path in the ``tf_op`` stat
    of its event *metadata* (one entry per distinct operation of a
    program), which ``jax.profiler.ProfileData`` does not expose; this
    reads the four message types it needs from the wire format (XSpace 1:
    planes; XPlane 2: name, 4: event metadata, 5: stat metadata;
    XEventMetadata 2: name, 5: stats; XStat 1: metadata id, 3/4: integer,
    5: string, 7: reference to a stat metadata's name)."""
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                entry = dict(_fields(buf, *v))
                if 2 in entry:
                    meta = dict(_fields(buf, *entry[2]))
                    stat_names[meta.get(1, 0)] = _text(buf, meta[2]) if 2 in meta else ""
        if not name.startswith("/device:"):
            continue
        table = out[name] = {}
        for v in events:
            entry = dict(_fields(buf, *v))
            if 2 not in entry:
                continue
            op, program, path = "", "", ""
            for f, x in _fields(buf, *entry[2]):
                if f == 2:
                    op = _text(buf, x)
                elif f == 5:
                    stat = dict(_fields(buf, *x))
                    kind = stat_names.get(stat.get(1))
                    if kind == "program_id":
                        program = str(stat.get(3, stat.get(4, "")))
                    elif kind == "tf_op":
                        path = (_text(buf, stat[5]) if 5 in stat
                                else stat_names.get(stat.get(7), ""))
            if program:
                table[(program, op)] = scope_of(path)
    return out


_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def device_plane(lines_by_name, table: Dict[Tuple[str, str], str]
                 ) -> Tuple[Dict[str, List[tr.Event]], Dict[Tuple[str, str], str]]:
    """One device plane's ``{"ops": [...], "modules": [...]}`` events, as
    ``trace_reduce.load`` keeps them, and the stage of each distinct
    (module execution name, operation) that ran, from ``table`` (keyed by
    program id, as ``op_scopes`` gives it). ``lines_by_name`` maps a line's
    name to its events (objects with ``name``, ``start_ns`` and
    ``duration_ns``)."""
    lines = {"ops": [], "modules": []}
    for e in lines_by_name.get("XLA Modules", ()):
        s = int(e.start_ns)
        lines["modules"].append((e.name, s, s + int(e.duration_ns)))
    mods = sorted(lines["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods]
    scopes: Dict[Tuple[str, str], str] = {}
    for e in lines_by_name.get("XLA Ops", ()):
        s, name = int(e.start_ns), e.name
        lines["ops"].append((name, s, s + int(e.duration_ns)))
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= mods[i][2]:
            continue
        key = (mods[i][0], name)
        if key not in scopes:
            m = _PROGRAM_ID.search(mods[i][0])
            scopes[key] = table.get((m.group(1) if m else "", name), "")
    return lines, scopes


def load(path: str) -> SpanTrace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        tables = op_scopes(memoryview(f.read()))
    data = ProfileData.from_file(path)
    devices, host, spans, window, scopes = {}, [], [], None, {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            devices[plane.name], scopes[plane.name] = device_plane(
                {line.name: line.events for line in plane.lines},
                tables.get(plane.name, {}))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    end = s + int(e.duration_ns)
                    if e.name == tr.WINDOW:
                        window = (s, end)
                    elif e.name.startswith(SPAN_PREFIX):
                        it = dict(e.stats).get("iteration")
                        spans.append(Span(e.name, s, end,
                                          None if it is None else int(it)))
                    m = tr._DISPATCH.match(e.name)
                    if m:
                        host.append((m.group(1), s, end))
    return SpanTrace(devices, host, window, spans, scopes)


def innermost(spans: List[Span]) -> List[Tuple[int, int, str]]:
    """Disjoint, sorted ``(start, end, name)`` segments: at each instant
    some span is open, the innermost one. Spans of one thread nest; a child
    that outlasts its parent is cut at the parent's end."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[str, int]] = []
    cur = 0

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    for sp in sorted(spans, key=lambda x: (x.start, -x.end)):
        while stack and stack[-1][1] <= sp.start:
            name, end = stack.pop()
            emit(cur, end, name)
            cur = end
        if stack:
            emit(cur, sp.start, stack[-1][0])
        stack.append((sp.name, min(sp.end, stack[-1][1]) if stack else sp.end))
        cur = sp.start
    while stack:
        name, end = stack.pop()
        emit(cur, end, name)
        cur = end
    return out


def span_times(spans: List[Span], t0: int, t1: int) -> Dict[str, dict]:
    """Per name: total and self seconds clipped to [t0, t1), and count."""
    out = defaultdict(lambda: {"total_s": 0.0, "self_s": 0.0, "count": 0})
    for sp in spans:
        s, e = max(sp.start, t0), min(sp.end, t1)
        if e > s:
            out[sp.name]["total_s"] += (e - s) * 1e-9
            out[sp.name]["count"] += 1
    for a, b, name in innermost(spans):
        a, b = max(a, t0), min(b, t1)
        if b > a:
            out[name]["self_s"] += (b - a) * 1e-9
    return dict(out)


class Gaps:
    """Disjoint, sorted idle intervals of one device, with the idle time
    before any instant at a bisection's cost."""

    def __init__(self, gaps: List[Tuple[int, int]]):
        self.gaps = gaps
        self.starts = [a for a, _ in gaps]
        self.prefix, run = [], 0
        for a, b in gaps:
            run += b - a
            self.prefix.append(run)

    def before(self, t: int) -> int:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        a, b = self.gaps[i - 1]
        return self.prefix[i - 1] - (b - a) + min(b, t) - a

    def inside(self, s: int, e: int) -> int:
        return self.before(e) - self.before(s)


def _staged(leaves: List[tr.Event], mods: List[tr.Event],
            table: Dict[Tuple[str, str], str]):
    """(program, stage, inherited, seconds) of each leaf operation.

    An operation the compiler inserted (a copy for a layout or for a buffer
    it may not overwrite) carries no name scope. It takes the stage of the
    next scoped operation of the same program execution, which is the one
    that needs it, or else of the last one before it (``inherited``)."""
    starts = [m[1] for m in mods]
    rows = []
    for name, s, e in leaves:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= mods[i][2]:
            rows.append([-1, "", "", False, (e - s) * 1e-9])
            continue
        rows.append([i, tr.program_name(mods[i][0]), table.get((mods[i][0], name), ""),
                     False, (e - s) * 1e-9])
    for order in (reversed(rows), rows):
        near = {}
        for row in order:
            if row[2]:
                near[row[0]] = row[2]
            elif row[0] >= 0 and row[0] in near:
                row[2], row[3] = near[row[0]], True
    return [tuple(r[1:]) for r in rows]


def reduce(trace: SpanTrace, top: int = 10) -> dict:
    red = tr.reduce(trace, top)
    t0, t1 = trace.window
    red["spans"] = span_times(trace.spans, t0, t1)
    segs = innermost(trace.spans)
    seg_ends = [b for _, b, _ in segs]
    host = sorted(tr._clip(trace.host, t0, t1), key=lambda e: e[1])
    host_starts = [s for _, s, _ in host]
    clipped = [sp._replace(start=max(sp.start, t0), end=min(sp.end, t1))
               for sp in trace.spans if min(sp.end, t1) > max(sp.start, t0)]
    scopes, by_prog = defaultdict(float), defaultdict(lambda: defaultdict(float))
    by_inherit = defaultdict(float)
    labelled, idle_by_span = defaultdict(float), defaultdict(float)
    spanned, active = 0.0, 0
    for plane, lines in trace.devices.items():
        dev_ops = tr._clip(lines["ops"], t0, t1)
        if not dev_ops:
            continue
        active += 1
        table = trace.scopes.get(plane, {})
        mods = sorted(tr._clip(lines["modules"], t0, t1), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for prog, stage, inherited, secs in _staged(tr.leaf_ops(dev_ops), mods, table):
            by_prog[prog][stage] += secs
            if stage:
                scopes[stage] += secs
            if inherited:
                by_inherit[prog] += secs
        busy = tr.union_intervals(dev_ops)
        edges = [t0] + [x for span in busy for x in span] + [t1]
        gaps = Gaps([(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a])
        for sp in clipped:
            idle_by_span[sp.name] += gaps.inside(sp.start, sp.end) * 1e-9
        for a, b, _ in segs:
            spanned += gaps.inside(max(a, t0), min(b, t1)) * 1e-9 if b > t0 and a < t1 else 0.0
        for a, b in gaps.gaps:
            i = bisect.bisect_right(host_starts, a) - 1
            old = host[i][0] if i >= 0 else "before the first dispatch"
            j = bisect.bisect_right(seg_ends, a)
            cur = a
            while j < len(segs) and segs[j][0] < b:
                sa, sb, name = segs[j]
                if sa > cur:
                    labelled[UNSPANNED + old] += (min(sa, b) - cur) * 1e-9
                lo, hi = max(sa, cur), min(sb, b)
                if hi > lo:
                    labelled[name] += (hi - lo) * 1e-9
                cur = max(cur, hi)
                j += 1
            if b > cur:
                labelled[UNSPANNED + old] += (b - cur) * 1e-9
    scale = 1.0 / active if active else 0.0
    red["scopes"] = {k: v * scale for k, v in scopes.items()}
    red["scopes_by_program"] = {p: {k: v * scale for k, v in d.items()}
                                for p, d in by_prog.items()}
    red["scopes_inherited"] = {k: v * scale for k, v in by_inherit.items()}
    red["idle_by_span"] = {k: v * scale for k, v in idle_by_span.items()}
    red["idle_spanned_s"] = spanned * scale
    if active:
        red["idle_gaps"] = sorted(((k, v * scale) for k, v in labelled.items()),
                                  key=lambda kv: -kv[1])[:top]
    return red

