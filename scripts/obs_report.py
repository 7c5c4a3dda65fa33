"""Export gossip-overlay telemetry to files: Perfetto trace + metrics JSONL.

    python scripts/obs_report.py [--nodes N] [--iterations I]
                                 [--engine ticks|events] [--bank]
                                 [--percentiles] [--out-prefix PREFIX]

Runs a small ``run_dagfl_gossip`` simulation with the in-loop collectors on
(``repro.obs``) and writes

* ``PREFIX.trace.json`` — Chrome Trace Event JSON. Open it at
  https://ui.perfetto.dev (or ``chrome://tracing``): one track per node
  showing iteration spans, row deliveries, and bank chunk drains, plus an
  overlay control track with partition windows (and, with
  ``--percentiles``, one ``hist:`` counter track per latency histogram);
* ``PREFIX.metrics.jsonl`` — one summary line (rounds, dispatch counts,
  final byte/staleness snapshot) followed by one line per in-loop sample
  (t, tips, staleness, rows_delta, chunk_lag, bytes_total). With
  ``--percentiles`` one ``"kind": "hist"`` line per histogram precedes
  the samples.

``--percentiles`` arms the streaming latency histograms
(``ObsConfig(hist=HistConfig())``) and prints a p50/p95/p99 summary per
histogram — publish->first-merge, publish->commit, chunk transfer delay
— with the bin-resolution error bound on each value.

The collectors run INSIDE the jitted loops as scan/while-loop carries, so
the export reflects exactly what the device executed — and the run is
bitwise identical to an uninstrumented one (see docs/OBSERVABILITY.md).

By default outputs land under ``bench_artifacts/`` (untracked — bench
sample artifacts are never committed); pass an explicit ``--out-prefix``
to write elsewhere.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--iterations", type=int, default=12)
    ap.add_argument("--engine", choices=("ticks", "events"), default="events")
    ap.add_argument("--bank", action="store_true",
                    help="gossip the model bank too (adds chunk-drain events)")
    ap.add_argument("--percentiles", action="store_true",
                    help="arm the streaming histograms and print the "
                         "p50/p95/p99 ladder per latency histogram")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out-prefix",
                    default=os.path.join("bench_artifacts", "obs_sample"))
    args = ap.parse_args(argv)

    from repro.fl.experiments import default_dagfl_config, make_cnn_setup
    from repro.fl.systems import SimConfig, run_dagfl_gossip
    from repro.net import gossip as gossip_lib
    from repro.net import topology as topo
    from repro.net.bank import BankGossipConfig
    from repro.obs import (HistConfig, ObsConfig, write_chrome_trace,
                           write_metrics_jsonl)

    n = args.nodes
    dcfg = default_dagfl_config(num_nodes=n)
    sim = SimConfig(iterations=args.iterations,
                    eval_every=max(args.iterations // 4, 1), seed=args.seed)
    task, nodes, gval, _ = make_cnn_setup(num_nodes=n, seed=args.seed)
    res = run_dagfl_gossip(
        task, nodes, dcfg, sim, gval,
        topology=topo.ring(n, link_latency=1.0, seed=args.seed),
        gossip=gossip_lib.GossipConfig(sync_period=1.0, seed=args.seed),
        engine=args.engine,
        bank_gossip=BankGossipConfig(chunks_per_slot=4) if args.bank else None,
        obs=ObsConfig(hist=HistConfig() if args.percentiles else None),
    )
    report = res.extras["obs"]
    out_dir = os.path.dirname(args.out_prefix)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    trace_path = f"{args.out_prefix}.trace.json"
    jsonl_path = f"{args.out_prefix}.metrics.jsonl"
    write_chrome_trace(report, trace_path)
    write_metrics_jsonl(report, jsonl_path)
    print(f"engine={report.engine} rounds={report.rounds} "
          f"samples={len(report.series['t'])} "
          f"trace_events={len(report.trace['t'])} "
          f"trace_dropped={report.trace_dropped} "
          f"dispatch={report.dispatch_counts}")
    if args.percentiles:
        for name, summ in report.hist["percentiles"].items():
            print(f"hist {name}: samples={summ['samples']} "
                  f"p50={summ['p50']:.4g}±{summ['p50_err']:.2g} "
                  f"p95={summ['p95']:.4g}±{summ['p95_err']:.2g} "
                  f"p99={summ['p99']:.4g}±{summ['p99_err']:.2g}")
    print(f"wrote {trace_path} (load at https://ui.perfetto.dev)")
    print(f"wrote {jsonl_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
