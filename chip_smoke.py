#!/usr/bin/env python3
"""Bring-up run of the DAG-FL main path on a TPU: proof that it still starts.

    python chip_smoke.py               # phases A-C on one chip
    python chip_smoke.py --chips 4     # the sharded replica axis on 4 chips
    python chip_smoke.py --rehearse    # tiny sizes on any backend, no result

Phase A  every main-path Pallas kernel, compiled, at paper widths, against
         its ``repro.kernels.ref`` oracle on the same chip. Selections and
         counts must match exactly; float reductions within a stated
         tolerance of the oracle run at full f32 matmul precision.
Phase B  the paper deployment through ``run_dagfl_gossip``: the full-size
         CNN (phi ~ 6.6 MB), 100 nodes, alpha=5, k=2, tau_max=20 s, every
         link priced at a Table-I class, the continuous-time engine with
         the model bank gossiped. The run is repeated with the lax oracles
         in place of the anti-entropy round's and the bank transfer's
         kernels; the two trajectories must be bitwise equal.
Phase C  the SPMD trainer (``repro.launch.train.run``) on qwen3-0.6b at full
         width: 3 nodes, batch 1 per node, 128 tokens, a few steps.

``--chips 4`` runs only Phase B on the tick engine, once with the replica
axis sharded over a 4-device "nodes" mesh and once unsharded, and checks
that the final replicas and bank state are bitwise equal.

Everything runs in this one process (a chip belongs to one process). The
script exits non-zero, and prints no result line, when JAX finds no TPU,
when the repo's sources are missing, or when any phase fails. Otherwise its
last line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Weights and data are random, made from ``--seed``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
TRAIN_STEPS = 3   # Phase C trainer steps
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(what)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling since ``reset``."""

    def __init__(self, jax):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += secs

    def reset(self) -> float:
        secs, self.secs = self.secs, 0.0
        return secs


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" not in stats:
        return "n/a"
    return (f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB "
            f"({stats.get('bytes_in_use', 0) / 2**30:.2f} GiB in use)")


# ---------------------------------------------------------------------------
# Phase A: kernels against their oracles
# ---------------------------------------------------------------------------


def phase_a(args, jax, on_tpu: bool) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.fl.tasks import CNNTask
    from repro.kernels import ref
    from repro.kernels.chunk_transfer import chunk_dedup_pallas
    from repro.kernels.delta_codec import (BLOCK, DeltaCodec, quant_blocks_pallas,
                                           topk_blocks_pallas)
    from repro.kernels.event_pop import event_pop_pallas
    from repro.kernels.fedavg import fedavg_pallas
    from repro.kernels.gossip_merge import gossip_winner_pallas
    from repro.kernels.hist_bincount import hist_bincount_pallas
    from repro.kernels.model_distance import model_distance_pallas
    from repro.obs.hist import HistConfig

    rng = np.random.default_rng(args.seed)
    r, cap, chunks = args.nodes, args.capacity, 4
    n_params = sum(
        math.prod(l.shape) for l in jax.tree_util.tree_leaves(
            jax.eval_shape(CNNTask().init, jax.random.PRNGKey(0))))
    if args.rehearse:
        n_params = 40_000
    alpha = 5
    failures = []

    def dev(x):
        return jax.device_put(np.asarray(x))

    def run(name, kernel, oracle, inputs, compare):
        t0 = time.perf_counter()
        compiled = jax.jit(kernel).lower(*inputs[0]).compile()
        t_compile = time.perf_counter() - t0
        require(not on_tpu or "tpu_custom_call" in compiled.as_text(),
                f"{name}: no compiled kernel in the program")
        t_run, details = 0.0, []
        for args_i in inputs:
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled(*args_i))
            t_run += time.perf_counter() - t0
            with jax.default_matmul_precision("highest"):
                want = jax.block_until_ready(jax.jit(oracle)(*args_i))
            ok, detail = compare(
                jax.tree_util.tree_map(np.asarray, out),
                jax.tree_util.tree_map(np.asarray, want))
            details.append(detail)
            if not ok:
                failures.append(name)
                break
        log(f"A {name}: compile {t_compile:.2f} s, run "
            f"{1e3 * t_run / len(details):.3f} ms/call, "
            f"{'FAIL' if name in failures else 'PASS'} "
            f"({'; '.join(details)})")

    def exact(out, want):
        out, want = (out, want) if isinstance(out, tuple) else ((out,), (want,))
        bad = sum(int(np.sum(np.asarray(o) != np.asarray(w)))
                  for o, w in zip(out, want))
        return bad == 0, f"exact: {bad} mismatching elements"

    def close(rel):
        def cmp(out, want):
            scale = float(np.max(np.abs(want))) or 1.0
            err = float(np.max(np.abs(out - want)))
            return err <= rel * scale, (
                f"max |err| {err:.3g} <= {rel:g} x max |oracle| {scale:.3g}")
        return cmp

    interp = not on_tpu

    # gossip winner: the whole receiver axis, and a 25-row shard block
    def keys(n):
        pub = rng.integers(-1, r, (n, cap)).astype(np.int32)
        t = np.where(pub >= 0, rng.integers(0, 4, (n, cap)) * 0.5, 0.0)
        return (dev(t.astype(np.float32)), dev(pub),
                dev(rng.integers(0, 6, (n, cap)).astype(np.int32)))

    winner_in = [keys(r) + (dev(rng.random((r, r)) < p),) for p in (0.05, 0.5, 1.0)]
    run(f"gossip_winner R={r} cap={cap}",
        lambda t, p, a, m: gossip_winner_pallas(t, p, a, m, interpret=interp),
        ref.gossip_winner_ref, winner_in, exact)
    blk = r // 4
    block_in = [keys(r) + (dev(rng.random((blk, r)) < 0.5),
                           dev(np.int32(off))) for off in (0, blk, r - blk)]
    run(f"gossip_winner block {blk}x{r} row_offset",
        lambda t, p, a, m, o: gossip_winner_pallas(
            t, p, a, m, interpret=interp, row_offset=o),
        lambda t, p, a, m, o: ref.gossip_winner_ref(
            t, p, a, m, row_ids=o + jnp.arange(blk, dtype=jnp.int32)),
        block_in, exact)

    # event-queue head: ties on (time, kind), unique seq, and an empty queue
    q = r * (r + 1)
    pop_in = []
    for frac in (0.5, 0.001, 0.0):
        pop_in.append((dev(rng.choice([0.25, 1.0, 1.5], q).astype(np.float32)),
                       dev(rng.integers(0, 5, q).astype(np.int32)),
                       dev(rng.permutation(q).astype(np.int32)),
                       dev(rng.random(q) < frac)))
    run(f"event_pop Q={q}",
        lambda t, k, s, v: event_pop_pallas(t, k, s, v, interpret=interp),
        ref.event_pop_ref, pop_in, exact)

    # content-addressed chunk dedup over the bank
    dedup_in = [(dev(rng.random((r, cap, chunks)) < 0.3),
                 dev(rng.integers(0, 8, (cap, chunks)).astype(np.float32)))]
    run(f"chunk_dedup R={r} S={cap} C={chunks}",
        lambda h, d: chunk_dedup_pallas(h, d, interpret=interp),
        ref.chunk_dedup_ref, dedup_in, exact)

    # Eq. (1) aggregation and pairwise distances over alpha CNN models
    models = dev(rng.normal(0, 0.05, (alpha, n_params)).astype(np.float32))
    w = rng.random(alpha).astype(np.float32)
    run(f"fedavg k={alpha} N={n_params}",
        lambda w_, m: fedavg_pallas(w_, m, interpret=interp),
        ref.fedavg_ref, [(dev(w / w.sum()), models)], close(1e-5))
    run(f"model_distance k={alpha} N={n_params}",
        lambda m: model_distance_pallas(m, interpret=interp),
        ref.model_distance_ref, [(models,)], close(1e-4))

    # streaming-histogram bincount: one sample per (node, ledger row)
    bins = HistConfig().bins + 1
    hist_in = [(dev(rng.integers(0, bins, r * cap).astype(np.int32)),
                dev(rng.integers(0, 4, r * cap).astype(np.int32)))]
    run(f"hist_bincount m={r * cap} bins={bins}",
        lambda i, w_: hist_bincount_pallas(i, w_, bins, interpret=interp),
        lambda i, w_: ref.hist_bincount_ref(i, w_, bins), hist_in, exact)

    # wire codec over one CNN payload in 128-element blocks
    nb = -(-n_params // BLOCK)
    x = dev(rng.normal(0, 0.05, (nb, BLOCK)).astype(np.float32))
    for name, qmax in (("int8", 127), ("int4", 7)):
        def codes_close(out, want):
            (c, s), (cr, sr) = out, want
            steps = np.abs(c.astype(np.int32) - cr.astype(np.int32))
            serr = float(np.max(np.abs(s - sr) / sr))
            ok = steps.max() <= 1 and serr <= 1e-6
            return ok, (f"scales rel err {serr:.3g} <= 1e-6; codes off by one "
                        f"step at {int(np.sum(steps > 0))} of {c.size}")
        run(f"quant_blocks {name} nb={nb}",
            lambda x_, qm=qmax: quant_blocks_pallas(x_, qm, interpret=interp),
            lambda x_, qm=qmax: ref.quant_blocks_ref(x_, qm), [(x,)],
            codes_close)
    k = DeltaCodec(kind="topk").topk_k()
    run(f"topk_blocks k={k} nb={nb}",
        lambda d: topk_blocks_pallas(d, k, interpret=interp),
        lambda d: ref.topk_blocks_ref(d, k), [(x,)], exact)
    require(not failures, f"kernels disagree with their oracles: {failures}")


# ---------------------------------------------------------------------------
# Phase B: the paper deployment through run_dagfl_gossip
# ---------------------------------------------------------------------------


def paper_setup(args):
    """Full-size CNN task, 100-node population and Table-I-priced overlay."""
    import numpy as np

    from repro.data.synthetic import MnistLike
    from repro.fl.experiments import default_dagfl_config
    from repro.fl.nodes import build_population
    from repro.fl.tasks import CNNTask
    from repro.net import topology as topo

    task = CNNTask()
    gen = MnistLike(image_size=28, seed=args.seed)
    nodes = build_population(gen, args.nodes, seed=args.seed)
    gval = gen.balanced(np.random.default_rng(args.seed + 31), 256)
    dcfg = dataclasses.replace(
        default_dagfl_config(args.nodes, "cnn"), capacity=args.capacity)
    # every link draws one of the finite Table-I bandwidth classes
    classes = [b for b in topo.TABLE1_LINK_CLASSES.values() if math.isfinite(b)]
    overlay = topo.full(args.nodes, seed=args.seed)
    draw = np.random.default_rng(args.seed + 5).choice(
        classes, (args.nodes, args.nodes))
    draw = np.triu(draw, 1) + np.triu(draw, 1).T
    overlay = overlay._replace(bandwidth=np.where(
        overlay.adjacency, draw, 0.0).astype(np.float32))
    return task, nodes, {"x": gval.x, "y": gval.y}, dcfg, overlay


def run_paper(args, engine, mesh=None, kernels=True):
    from repro.fl.systems import SimConfig, run_dagfl_gossip
    from repro.kernels import dispatch
    from repro.net import gossip as gossip_lib
    from repro.net.bank import BankGossipConfig

    task, nodes, gval, dcfg, overlay = paper_setup(args)
    sim = SimConfig(iterations=args.iterations, eval_every=args.eval_every,
                    seed=args.seed)
    # kernels: the default dispatch on a TPU; a rehearsal elsewhere forces
    # the (interpreted) kernels so that it exercises them in the loop too
    impl = (None if dispatch.on_tpu() else "pallas") if kernels else "lax"
    return run_dagfl_gossip(
        task, nodes, dcfg, sim, gval, topology=overlay,
        gossip=gossip_lib.GossipConfig(sync_period=1.0, seed=args.seed,
                                       impl=impl or "fused"),
        bank_gossip=BankGossipConfig(impl=impl), engine=engine, mesh=mesh,
    )


def check_run(res, args, jax) -> str:
    """What a finished paper run must show; returns a one-line summary."""
    import numpy as np

    # the last publisher slot is the external agent's genesis transaction
    committed = int(np.sum(res.extras["published"][:-1]))
    require(committed == args.iterations,
            f"committed {committed} of {args.iterations} iterations")
    require(len(res.accs) >= 2 and bool(np.all((res.accs >= 0) & (res.accs <= 1))),
            f"accuracy curve {res.accs}")
    finite = all(bool(np.all(np.isfinite(np.asarray(l))))
                 for l in jax.tree_util.tree_leaves(res.final_params))
    require(finite, "final model has non-finite parameters")
    sent = float(res.extras["bank_bytes_sent"])
    require(sent > 0, "no model payload crossed a link")
    return (f"iterations committed {committed}, evals at {res.iters.tolist()}, "
            f"final accuracy {float(res.accs[-1]):.4f}, sim time "
            f"{float(res.times[-1]):.1f} s, events {res.extras['events_processed']}, "
            f"sync rounds {res.extras['sync_rounds']}, device calls "
            f"{res.extras['device_calls']}, bank bytes {sent:.4g}, "
            f"chunks missing at end {int(np.max(res.extras['bank_missing_final']))}")


def same_run(a, b, jax) -> list:
    """Names of the result fields where two runs differ bitwise."""
    import numpy as np

    diff = [n for n in ("accs", "times", "iters")
            if not np.array_equal(getattr(a, n), getattr(b, n))]
    for tag, x, y in (("replicas", a.extras["replicas"], b.extras["replicas"]),
                      ("final_params", a.final_params, b.final_params)):
        lx, ly = (jax.tree_util.tree_leaves_with_path(t) for t in (x, y))
        diff += [f"{tag}{jax.tree_util.keystr(p)}"
                 for (p, u), (_, v) in zip(lx, ly)
                 if not np.array_equal(np.asarray(u), np.asarray(v))]
    return diff


def phase_b(args, jax, clock, dev) -> None:
    from repro.kernels import dispatch

    on_tpu = dispatch.on_tpu()
    if on_tpu:
        require(dispatch.pick_impl(None, "smoke") == "pallas"
                and not dispatch.interpret_mode(),
                "the TPU dispatch rule must pick compiled kernels")
    t0 = time.perf_counter()
    res = run_paper(args, "events")
    wall = time.perf_counter() - t0
    log(f"B kernels: wall {wall:.1f} s (compile {clock.reset():.1f} s), "
        f"peak {peak_bytes(dev)}; {check_run(res, args, jax)}")
    t0 = time.perf_counter()
    ref = run_paper(args, "events", kernels=False)
    wall = time.perf_counter() - t0
    log(f"B lax oracles: wall {wall:.1f} s (compile {clock.reset():.1f} s); "
        f"{check_run(ref, args, jax)}")
    diff = same_run(res, ref, jax)
    log(f"B kernel run vs oracle run: "
        f"{'bitwise equal' if not diff else 'DIFFER in ' + ', '.join(diff)}")
    require(not diff, "the two runs differ")


def phase_b_mesh(args, jax, clock, dev) -> None:
    from repro.net.mesh import make_gossip_mesh

    mesh = make_gossip_mesh(args.chips)
    t0 = time.perf_counter()
    sharded = run_paper(args, "ticks", mesh=mesh)
    wall = time.perf_counter() - t0
    log(f"B ticks, replica axis over {args.chips} devices: wall {wall:.1f} s "
        f"(compile {clock.reset():.1f} s), peak {peak_bytes(dev)}; "
        f"{check_run(sharded, args, jax)}")
    t0 = time.perf_counter()
    single = run_paper(args, "ticks")
    wall = time.perf_counter() - t0
    log(f"B ticks, one device: wall {wall:.1f} s "
        f"(compile {clock.reset():.1f} s); {check_run(single, args, jax)}")
    diff = same_run(sharded, single, jax)
    log(f"B sharded vs single-device replicas and bank state: "
        f"{'bitwise equal' if not diff else 'DIFFER in ' + ', '.join(diff)}")
    require(not diff, "the two runs differ")


# ---------------------------------------------------------------------------
# Phase C: the SPMD trainer on a registry model at full width
# ---------------------------------------------------------------------------


def phase_c(args, jax, clock, dev) -> None:
    import numpy as np

    from repro.configs import get_arch
    from repro.launch.train import run

    cfg = get_arch("qwen3-0.6b")
    seq = 128
    if args.rehearse:
        cfg, seq = cfg.reduced(), 32
    t0 = time.perf_counter()
    stacked, frontier, metrics = run(
        cfg, steps=TRAIN_STEPS, nodes=3, batch_per_node=1, seq_len=seq,
        seed=args.seed, log_every=1)
    finite = all(bool(jax.numpy.all(jax.numpy.isfinite(l)))
                 for l in jax.tree_util.tree_leaves(stacked))
    wall = time.perf_counter() - t0
    acc = float(metrics["mean_val_acc"])
    published = np.asarray(frontier.total_published).tolist()
    log(f"C {cfg.name} nodes=3 batch/node=1 seq={seq}: wall {wall:.1f} s "
        f"(compile {clock.reset():.1f} s), peak {peak_bytes(dev)}, "
        f"mean_val_acc {acc:.4f}, published {published}, params finite {finite}")
    require(finite and 0.0 <= acc <= 1.0, "trainer output out of range")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no result line")
    args = ap.parse_args(argv)
    args.nodes, args.capacity, args.iterations = (
        (8, 32, 8) if args.rehearse else (100, 192, 40))
    args.eval_every = args.iterations // 2

    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are missing: {e}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log(f"devices: {device}, jax {jax.__version__}, compile cache {cache}")
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    clock = CompileClock(jax)
    if args.chips > 1:
        phases = [("B-mesh", lambda: phase_b_mesh(args, jax, clock, dev))]
    else:
        phases = [("A", lambda: phase_a(args, jax, dev.platform == "tpu")),
                  ("B", lambda: phase_b(args, jax, clock, dev)),
                  ("C", lambda: phase_c(args, jax, clock, dev))]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        clock.reset()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        gc.collect()
        log(f"phase {name}: {'FAILED' if name in failed else 'ok'} in "
            f"{time.perf_counter() - t0:.1f} s")
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    if args.rehearse:
        log("rehearsal passed (not a chip run)")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
