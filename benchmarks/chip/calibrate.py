#!/usr/bin/env python3
"""Readings behind the limits of ``correct``: the program and its control.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control high|default|bf16] > readings.jsonl

One process, on whatever device JAX finds (the readings name its
platform). For each seed it runs the window's first episode of that seed
through the timed path (the same ``harness.episode_runner``), replays it
with the plain reference (``reference.replay``) and applies the
benchmark's own comparison (``reference.compare``), printing one JSON line
per seed: the readings, each compared number beside its limit, and
``correct``. The process runs at the configuration's ``matmul_precision``,
as the benchmark does; without ``--control`` so does the program.
``--control`` runs the control in the program's place instead:

* ``high``     the program with JAX's next precision below ``highest``
               switched on (three bfloat16 passes per f32 product);
* ``default``  the program at the TPU's default precision (one bfloat16
               pass per f32 product), the path it runs without the
               configuration's setting;
* ``bf16``     the reference's own model, loss and SGD step computed in
               bfloat16, put in the place of the program's task.

The benchmark's own runs never run this script. Its readings set the
configuration's ``correct_limits`` (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import workload as wl  # noqa: E402


def bf16_task(cell, jax, base):
    """``base``, the program's task, with the reference's model computed in
    bfloat16 in its place."""
    import jax.numpy as jnp

    ref, lr = cell.task, cell.config["model"]["learning_rate"]

    def to16(p):
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), p)

    class Bf16Task(type(base)):
        def eval_fn(self, params, batch):
            return ref.accuracy(to16(params), batch, None)

        def train_fn(self, params, batch, key):
            p16 = to16(params)
            loss, g = jax.value_and_grad(ref.loss)(p16, batch, None)
            new = jax.tree_util.tree_map(lambda p, d: (p - lr * d).astype(jnp.float32),
                                         p16, g)
            return new, {"loss": loss}

    return Bf16Task(**{f: getattr(base, f) for f in base.__dataclass_fields__})


def reading(cell, seed: int, control, jax) -> dict:
    """One seed's readings: the window's first episode of ``seed`` on the
    timed path (or, under ``control``, its control) and the reference's
    replay of it, with the numbers the benchmark compares, their limits and
    the ``correct`` that the benchmark's own comparison gives."""
    import harness
    import reference

    precision = {"high": "high", "default": "default"}.get(
        control, cell.config["matmul_precision"])
    with jax.default_matmul_precision(precision):
        if control == "bf16":
            orig = cell.task.program_task
            cell.task.program_task = lambda mod, model: bf16_task(cell, jax,
                                                                   orig(mod, model))
            try:
                dep, overlay, run_episode = harness.episode_runner(cell, seed)
            finally:
                cell.task.program_task = orig
        else:
            dep, overlay, run_episode = harness.episode_runner(cell, seed)
        t = time.perf_counter()
        res = run_episode(1)
        t_prog = time.perf_counter() - t
    t = time.perf_counter()
    readings = reference.replay(cell, dep, overlay, wl.episode_seed(seed, 1), res, jax)
    compared = reference.compare(cell, readings)
    return {"workload": cell.name, "seed": seed, "control": control,
            "precision": precision, "platform": jax.devices()[0].platform,
            "episode_s": t_prog, "replay_s": time.perf_counter() - t, **readings,
            "correct": reference.is_correct(compared), "compared": compared}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", choices=("high", "default", "bf16"), default=None)
    args = ap.parse_args(argv)
    cell = wl.load_cell(ROOT, args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision", cell.config["matmul_precision"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        print(json.dumps(reading(cell, seed, args.control, jax)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
