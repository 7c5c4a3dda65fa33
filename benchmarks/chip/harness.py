"""One benchmark run of a cell: set-up, warm-up, window, trace, check.

``run_cell`` is everything ``run.py`` does once JAX holds a TPU; the tests
drive it on the CPU at a tiny size with the chip check skipped.
"""
from __future__ import annotations

import json
import os
import shutil
import time
from typing import Callable, Optional

import workload as wl

HERE = os.path.dirname(os.path.abspath(__file__))


def episode_runner(cell: wl.Cell, seed: int):
    """(deployment, run_episode(e) -> SimResult) for ``cell`` under ``seed``.

    Each episode is one call of the program's entry point, the way a user
    calls it: ``run_dagfl_gossip`` on the events engine, fresh nodes, the
    episode's seed in the simulation, the gossip protocol and the nodes.
    """
    from repro.configs import base
    from repro.fl import systems, tasks
    from repro.net import gossip as gossip_lib
    from repro.net import topology as topo
    from repro.net.bank import BankGossipConfig

    dep = wl.Episodes(cell, seed)
    task = cell.task.program_task(tasks, cell.config["model"])
    dcfg = wl.dagfl_config(base, cell.config)
    overlay = dep.overlay(topo)
    tr = cell.traffic
    bank = (BankGossipConfig(chunks_per_slot=int(tr["chunks_per_slot"]))
            if tr["bank_gossip"] else None)

    def run_episode(e: int):
        s = wl.episode_seed(seed, e)
        sim = systems.SimConfig(iterations=cell.iterations,
                                eval_every=cell.eval_every, seed=s,
                                **cell.config["sim"])
        gossip = gossip_lib.GossipConfig(
            sync_period=float(tr["sync_period_s"]), seed=s,
            max_events_per_advance=int(tr["max_events_per_advance"]),
            max_ticks_per_advance=int(tr["max_ticks_per_advance"]))
        return systems.run_dagfl_gossip(
            task, dep.nodes(s), dcfg, sim, dep.gval, topology=overlay,
            gossip=gossip, bank_gossip=bank, engine=tr["engine"])

    return dep, overlay, run_episode


def committed(res) -> int:
    """Committed iterations of one episode (the last publisher slot is the
    external agent's genesis transaction)."""
    import numpy as np

    return int(np.sum(np.asarray(res.extras["published"])[:-1]))


def sampled_episode(seed: int) -> int:
    """Which of the window's first two episodes the reference replays."""
    return 1 + wl.episode_seed(seed, 1 << 30) % 2


def peak_bytes(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def load_peaks(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise KeyError(f"no peak figures for device kind {kind!r} in peaks.json")
    return table[kind]


def read_metrics(cell: wl.Cell, ctx: dict) -> dict:
    """Each per-layer metric's reader (``metrics/<name>.py``) on ``ctx``; a
    reader that finds nothing returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        mod = wl.load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(cell: wl.Cell, args, jax, counter, t_process: float,
             trace_dir: Optional[str], log: Callable[[str], None]) -> dict:
    import reference
    import trace_reduce

    # the configuration's stated float32: matmuls and convolutions at full
    # precision (a TPU's default runs f32 products in bfloat16 passes)
    jax.config.update("jax_default_matmul_precision", cell.config["matmul_precision"])
    t = time.perf_counter()
    dep, overlay, run_episode = episode_runner(cell, args.seed)
    t_dep = time.perf_counter()
    warm = run_episode(0)
    log(f"set-up: {t - t_process:.1f} s to a device, {t_dep - t:.1f} s of data and "
        f"overlay, {time.perf_counter() - t_dep:.1f} s of warm-up episode "
        f"({committed(warm)} of {cell.iterations} committed)")
    del warm
    counter.reset()
    pick = sampled_episode(args.seed)

    t_window = time.perf_counter()
    setup_s = t_window - t_process
    episodes, done, kept, traced = 0, 0, None, None
    # with the trace on, the window goes on past the traced episode until at
    # least one episode has run without the profiler: mfu.sim reads the rate
    # of those episodes alone, from the trace's stop to the last one's end
    t_free, free_done, free_wall = None, 0, 0.0
    while (not episodes or time.perf_counter() - t_window < args.seconds
           or (trace_dir is not None and not free_wall)):
        if trace_dir is not None and not episodes:
            # the trace covers the window's first episode: a bounded amount
            # of work, so the profiler's event buffer never fills
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                res = run_episode(1)
            traced = (committed(res), int(res.extras["device_calls"]))
            jax.profiler.stop_trace()
            t_free = time.perf_counter()
        else:
            res = run_episode(1 + episodes)
            if t_free is not None:
                free_done += committed(res)
                free_wall = time.perf_counter() - t_free
        episodes += 1
        done += committed(res)
        if episodes == pick or (kept is None and episodes == 1):
            kept = (episodes, res)
        del res
    wall = time.perf_counter() - t_window
    compiled, compile_s = counter.reset()
    log(f"window: {episodes} episodes, {done} of {episodes * cell.iterations} "
        f"iterations committed in {wall:.3f} s; {len(compiled)} programs compiled "
        f"or loaded from the cache in the window ({compile_s:.3f} s): "
        f"{sorted(set(compiled))}")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes(jax)}

    result = {"correct": False, "attempted": episodes * cell.iterations,
              "failed": episodes * cell.iterations - done}
    if trace_dir is None:
        result["metrics"] = {
            "iters_per_s": {"value": done / wall, "unit": "iters/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
    else:
        path = trace_reduce.find_xplane(trace_dir)
        red = trace_reduce.reduce(trace_reduce.load(path))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"cell": cell, "trace": red, "committed": traced[0],
               "device_calls": traced[1],
               "untraced_committed": free_done, "untraced_wall_s": free_wall,
               "peaks": load_peaks(dev.device_kind),
               "flops": cell.task.flops(cell.config, cell.eval_every, cell.iterations)}
        result["metrics"] = read_metrics(cell, ctx)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": [list(x) for x in red["device_ops"]],
                               "idle_gaps": [list(x) for x in red["idle_gaps"]]}
    result["device"] = device

    ep, res = kept
    seed_e = wl.episode_seed(args.seed, ep)
    t = time.perf_counter()
    compared = reference.check(cell, dep, overlay, seed_e, res, jax, log)
    log(f"reference replay of window episode {ep} (seed {seed_e}): "
        f"{time.perf_counter() - t:.1f} s")
    result["correct"] = reference.is_correct(compared)
    for name, c in compared.items():
        log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    result["compared"] = compared
    return result
