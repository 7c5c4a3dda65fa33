#!/usr/bin/env python3
"""Chip benchmark of the DAG-FL simulator: committed iterations per second.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process on the machine that holds the cell's chips. Set-up builds the
cell's deployment from ``--seed`` (population data, global validation set,
Table-I overlay), then runs one warm-up episode so that every program the
window uses is compiled. The window runs whole episodes of
``repro.fl.systems.run_dagfl_gossip`` back to back, each fixed by
``--seed`` and its index, until ``--seconds`` have passed. After the window
one episode drawn from the seed is replayed by the plain reference
(``reference.py``) and every number it compares is printed beside its
limit. ``--trace 1`` records a profiler trace of the window's first episode,
runs on until at least one more episode has run untraced, and reports the
per-layer metrics instead of the end-to-end ones.

The last line of standard output is the result, one JSON object. Without a
TPU, or with fewer chips than the cell asks for, or without the program's
sources, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import workload as wl  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Programs JAX compiled or loaded from its persistent cache since
    ``reset`` (its backend-compile monitoring events), by name."""

    def __init__(self, jax):
        self.names, self.secs = [], 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(fun_name)
            self.secs += secs

    def reset(self):
        out = (self.names, self.secs)
        self.names, self.secs = [], 0.0
        return out


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        cell = wl.load_cell(ROOT, args.workload)
    except (OSError, KeyError, ValueError) as e:
        log(f"run.py: cannot load workload {args.workload!r}: {e}")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import harness
    except ImportError as e:
        log(f"run.py: the program's sources are missing: {e}")
        return 2

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    import jax

    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    # keep every program, however small or quick to compile, in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        log(f"run.py: JAX found no devices: {e}")
        return 1
    if devices[0].platform != "tpu":
        log(f"run.py: no TPU (platform {devices[0].platform!r})")
        return 1
    if len(devices) < cell.chips:
        log(f"run.py: the cell needs {cell.chips} chips, JAX found {len(devices)}")
        return 1
    log(f"devices {devices[0].device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache}")
    counter = CompileCounter(jax)
    result = harness.run_cell(cell, args, jax, counter, T_PROCESS,
                              trace_dir=tempfile.mkdtemp(prefix="dagfl_trace_")
                              if args.trace else None, log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
