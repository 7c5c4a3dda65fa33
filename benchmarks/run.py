"""Benchmark harness: one module per paper table/figure.

    python -m benchmarks.run [--quick] [--only NAME]

Emits ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).
Scale note: the simulation benches run the paper's experiments at bench
scale (100 nodes, 300-400 iterations vs the paper's 5000-10000, scaled-down
CNN/LSTM on synthetic data) — trends and orderings are the reproduction
target; see EXPERIMENTS.md.
"""
import argparse
import importlib.util
import os
import sys
import time
import traceback

from benchmarks.common import header
from benchmarks import (
    ablation_weighted,
    fig5_ideal_convergence,
    fig6_11_abnormal_nodes,
    gossip_propagation,
    kernel_bench,
    roofline_table,
    serve_load,
    stability_tips,
    table2_iteration_delay,
    table3_attack_success,
    table4_contribution_rates,
)
from repro.compile_cache import enable_compile_cache


def _obs_report(*argv: str) -> int:
    """Run ``scripts/obs_report.py`` in this process: a child process could
    not reach a chip this process already holds."""
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "scripts", "obs_report.py")
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main(list(argv))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="reduced iteration counts")
    ap.add_argument("--only", help="run a single bench by prefix")
    args = ap.parse_args()
    enable_compile_cache()

    # defaults sized for the CPU container (~45 min total); the paper-scale
    # sweep is the same code with larger counts (EXPERIMENTS.md notes scale)
    iters_long = 150 if args.quick else 250
    iters_mid = 100 if args.quick else 200
    # the LSTM task's sequential 80-step scan is ~4x the CNN cost per
    # iteration on CPU; its benches run shorter (trend-sufficient)
    iters_lstm = 60 if args.quick else 150
    counts = (20,) if args.quick else (5, 20)

    benches = [
        ("stability", lambda: stability_tips.run()),
        ("kernels", lambda: kernel_bench.run()),
        ("table2", lambda: (
            table2_iteration_delay.run("cnn", 100),
            table2_iteration_delay.run("lstm", 100),
        )),
        ("fig5", lambda: (
            fig5_ideal_convergence.run("cnn", iters_long),
            fig5_ideal_convergence.run("lstm", iters_lstm),
        )),
        ("fig6", lambda: fig6_11_abnormal_nodes.run_dagfl_sweep("cnn", iters_mid, counts=counts)),
        ("fig7_10", lambda: (
            fig6_11_abnormal_nodes.run_four_systems("cnn", "lazy", 20, iters_mid),
            fig6_11_abnormal_nodes.run_four_systems("cnn", "poisoning", 20, iters_mid),
            fig6_11_abnormal_nodes.run_four_systems("cnn", "backdoor", 20, iters_mid),
            fig6_11_abnormal_nodes.run_four_systems("lstm", "poisoning", 20, iters_lstm),
        )),
        # sync fast path: impl x N x cap round grid + dispatch batching,
        # written to BENCH_gossip_sync.json
        ("gossip_sync", lambda: gossip_propagation.run_sync_bench()),
        # continuous-time event engine: tick-limit equivalence, per-edge
        # latency propagation, in-system Eq. (4). Already part of
        # gossip_sync — the standalone entry exists only for targeted
        # --only runs, so a default full run doesn't execute it twice.
        *([("event_engine", lambda: gossip_propagation.run_event_engine())]
          if args.only else []),
        # in-loop telemetry: obs-off bitwise equivalence + collector
        # overhead. Already part of gossip_sync; same targeted-run rule.
        *([("observability", lambda: gossip_propagation.run_observability())]
          if args.only else []),
        # adversarial fault layer: all-honest bitwise equivalence + the
        # spoof-defense tripwire (BENCH_gossip_sync.json "attack_suite").
        # Already part of gossip_sync; same targeted-run rule.
        *([("attack_suite", lambda: gossip_propagation.run_fault_suite())]
          if args.only else []),
        # wire compression: identity-codec bitwise equivalence + the
        # accuracy-vs-bytes Pareto sweep (BENCH_gossip_sync.json
        # "delta_codec"). Already part of gossip_sync; same targeted-run
        # rule.
        *([("delta_codec", lambda: gossip_propagation.run_delta_codec())]
          if args.only else []),
        # Poisson inference load on the event engine: zero-rate bitwise
        # equivalence + requests/s and staleness-at-serve percentiles
        # across Table-I link classes and a partition arm
        # (BENCH_gossip_sync.json "serve_load"). Already part of
        # gossip_sync; same targeted-run rule.
        *([("serve_load", lambda: serve_load.run_serve_load())]
          if args.only else []),
        # demo: write a Perfetto trace + metrics JSONL from a small sim
        *([("obs_report", lambda: _obs_report("--iterations", "10"))]
          if args.only else []),
        ("gossip", lambda: (
            gossip_propagation.run_sweep(iters_mid),
            gossip_propagation.run_partition(iters_mid),
        )),
        ("table3", lambda: (
            table3_attack_success.run(iters_mid),
            table3_attack_success.run_transport(iters_mid // 4),
        )),
        ("table4", lambda: table4_contribution_rates.run("cnn", iters_mid, counts=counts)),
        ("ablation", lambda: ablation_weighted.run(150 if args.quick else 200)),
        ("roofline", lambda: roofline_table.run()),
    ]

    header()
    failures = []
    t0 = time.time()
    for name, fn in benches:
        if args.only and not name.startswith(args.only):
            continue
        try:
            fn()
        except Exception as e:
            failures.append((name, repr(e)))
            traceback.print_exc()
    print(f"# total_bench_time_s,{time.time()-t0:.1f}")
    if failures:
        for f in failures:
            print(f"# FAILED,{f[0]},{f[1]}")
        sys.exit(1)


if __name__ == "__main__":
    main()
