"""DAG-FL training driver — the end-to-end production path.

    python -m repro.launch.train --arch qwen3-0.6b --reduced \
        --steps 50 --nodes 4

Runs the jitted ``dagfl_train_step`` (selection -> Eq.-1 aggregation ->
local train -> cross-validation scoring -> frontier publish) on whatever
mesh the host provides (1 CPU device here; the same code lowers on the
16x16 / 2x16x16 production meshes — see repro.launch.dryrun).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_pytree
from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch, list_archs
from repro.configs.base import DagFLConfig, ModelConfig, TrainConfig
from repro.data.pipeline import TokenSampler
from repro.models import build_model
from repro.sharding import fl_step as fl_lib


def small_100m() -> ModelConfig:
    """~100M-param dense config for the end-to-end example driver."""
    import dataclasses

    return dataclasses.replace(
        get_arch("qwen3-0.6b"),
        name="qwen3-100m",
        num_layers=8,
        d_model=512,
        num_heads=8,
        num_kv_heads=4,
        head_dim=64,
        d_ff=1536,
        vocab_size=32768,
        dtype="float32",
    )


def run(
    cfg: ModelConfig,
    steps: int = 50,
    nodes: int = 4,
    batch_per_node: int = 4,
    seq_len: int = 128,
    lr: float = 3e-3,
    seed: int = 0,
    log_every: int = 10,
    checkpoint: str = "",
):
    model = build_model(cfg)
    tcfg = TrainConfig(optimizer="sgd", learning_rate=lr)
    dcfg = DagFLConfig(num_nodes=nodes, alpha=min(4, nodes), k=2, tau_max=1e9)
    # the loop rebinds the stacked params and the frontier every step, so
    # the step may reuse their buffers (a full-width model then fits one chip)
    step_fn = jax.jit(
        fl_lib.make_dagfl_train_step(model, cfg, tcfg, dcfg, nodes),
        donate_argnums=(0, 1),
    )

    key = jax.random.PRNGKey(seed)
    init_keys = jax.random.split(key, nodes)
    stacked = jax.jit(jax.vmap(model.init))(init_keys)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(stacked)) // nodes
    print(f"arch={cfg.name} params/node={n_params/1e6:.1f}M nodes={nodes} "
          f"batch/node={batch_per_node} seq={seq_len}")

    frontier = fl_lib.init_frontier(nodes)
    samplers = [
        TokenSampler(cfg.vocab_size, batch_per_node, seq_len, seed=seed + i)
        for i in range(nodes)
    ]
    val = TokenSampler(cfg.vocab_size, 1, min(seq_len, 512), seed=seed + 999)
    val_tokens = jnp.stack([jnp.asarray(val.next()["tokens"][0]) for _ in range(nodes)])
    val_batch = {"tokens": val_tokens[:, None, :]}

    metrics = {}
    t0 = time.time()
    for step in range(steps):
        toks = np.stack([s.next()["tokens"] for s in samplers])   # (N, b, S)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
        if cfg.frontend_tokens:
            batch["frontend"] = jnp.zeros(
                (nodes, batch_per_node, cfg.frontend_tokens, cfg.frontend_dim)
            )
            val_batch.setdefault(
                "frontend",
                jnp.zeros((nodes, 1, cfg.frontend_tokens, cfg.frontend_dim)),
            )
        stacked, frontier, metrics = step_fn(
            stacked, frontier, batch, val_batch, jax.random.PRNGKey(seed * 7 + step)
        )
        if (step + 1) % log_every == 0 or step == 0:
            dt = (time.time() - t0) / (step + 1)
            print(f"step {step+1:4d}  mean_val_acc={float(metrics['mean_val_acc']):.4f}  "
                  f"sel_entropy={float(metrics['selection_entropy']):.3f}  "
                  f"{dt:.2f}s/step")
    if checkpoint:
        save_pytree(checkpoint, {"params": stacked, "frontier": frontier},
                    meta={"arch": cfg.name, "steps": steps})
        print(f"checkpoint -> {checkpoint}.npz")
    return stacked, frontier, metrics


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list_archs() + ["100m"])
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch-per-node", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args()
    enable_compile_cache()

    if args.arch == "100m":
        cfg = small_100m()
    else:
        cfg = get_arch(args.arch)
        if args.reduced:
            cfg = cfg.reduced()
    run(cfg, args.steps, args.nodes, args.batch_per_node, args.seq_len,
        args.lr, checkpoint=args.checkpoint)


if __name__ == "__main__":
    main()
