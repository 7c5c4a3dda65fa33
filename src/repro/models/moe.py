"""Mixture-of-Experts block: shared + routed experts, top-k routing.

Three dispatch implementations:

* ``dense``  — every expert runs on every token, gates mask the combine.
               O(T*E*d*ff) compute: only sane at smoke scale (E <= 4) and as
               the oracle the sorted path is tested against.
* ``sorted`` — MaxText/MegaBlocks-style: sort token-expert pairs by expert,
               capacity-bucket into an (E, C, d) buffer, one grouped einsum
               per projection, gather+segment-sum combine. O(k*T*d*ff).
               This is the production path; the distribution layer shards the
               expert dimension over the ``model`` mesh axis (expert
               parallelism) so the scatter/gather becomes the MoE all-to-all.
* ``ragged`` — dropless, for the experts this device holds
               (``cfg.expert_offset``, ``cfg.experts_held``): the router
               scores all ``num_experts``, the (token, expert) pairs routed to
               a held expert are sorted by expert and go through one grouped
               matmul per projection (``jax.lax.ragged_dot``), whose work is
               that of those pairs alone. No pair is dropped. The result is
               this device's share: what the absent experts add comes from
               the devices that hold them.

Router: softmax over all experts, top-k, and with ``cfg.norm_topk_prob`` the k
gates renormalised (DeepSeek-V2's ``MoEGate``, greedy top-k), plus the
switch-transformer load-balance auxiliary loss.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init, mlp_apply, mlp_init


def moe_init(key, cfg: ModelConfig, dtype=None) -> dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ff = cfg.moe_d_ff or cfg.d_ff
    E = cfg.resolved_experts_held()
    kr, ki, kg, ko, ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)

    def stack(k, fan_in, fan_out):
        w = jax.random.truncated_normal(k, -2.0, 2.0, (E, fan_in, fan_out)) * scale
        return w.astype(dtype)

    p = {
        "router": dense_init(kr, d, cfg.num_experts, dtype),
        "wi": stack(ki, d, ff),
        "wo": stack(ko, ff, d) * math.sqrt(d) / math.sqrt(ff),
    }
    if cfg.act in ("swiglu", "geglu"):
        p["wg"] = stack(kg, d, ff)
    if cfg.num_shared_experts:
        import dataclasses

        shared_cfg = dataclasses.replace(cfg, d_ff=cfg.num_shared_experts * ff)
        p["shared"] = mlp_init(ks, shared_cfg, dtype=dtype)
    return p


def _expert_ffn(cfg: ModelConfig, params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """x: (E, C, d) -> (E, C, d), one grouped matmul per projection."""
    h = jnp.einsum("ecd,edf->ecf", x, params["wi"])
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, params["wg"])) * h
    elif cfg.act == "geglu":
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, params["wg"]), approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    return jnp.einsum("ecf,efd->ecd", h, params["wo"])


def route(cfg: ModelConfig, params: dict, x: jnp.ndarray):
    """x: (T, d) -> gates (T, k), expert ids (T, k), aux loss ()."""
    logits = (x @ params["router"]).astype(jnp.float32)     # (T, E)
    k = cfg.experts_per_token
    probs = jax.nn.softmax(logits, axis=-1)
    if cfg.norm_topk_prob:
        # softmax over all, top-k, renormalised == softmax over the top-k logits
        top_logits, top_idx = jax.lax.top_k(logits, k)
        gates = jax.nn.softmax(top_logits, axis=-1)
    else:
        gates, top_idx = jax.lax.top_k(probs, k)

    # switch load-balance aux: E * sum_e load_e * importance_e
    importance = jnp.mean(probs, axis=0)                    # (E,)
    load = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_idx, cfg.num_experts, dtype=jnp.float32), axis=1),
        axis=0,
    ) / k
    aux = cfg.num_experts * jnp.sum(importance * load)
    return gates.astype(x.dtype), top_idx, aux


def capacity(cfg: ModelConfig, num_tokens: int, factor: float = 1.25) -> int:
    c = int(math.ceil(num_tokens * cfg.experts_per_token / cfg.num_experts * factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly tiles


def moe_apply_dense(cfg: ModelConfig, params: dict, x: jnp.ndarray):
    """Oracle path: all experts on all tokens. x (T, d)."""
    gates, top_idx, aux = route(cfg, params, x)
    combine = jnp.zeros((x.shape[0], cfg.num_experts), x.dtype)
    combine = jnp.sum(
        jax.nn.one_hot(top_idx, cfg.num_experts, dtype=x.dtype) * gates[..., None], axis=1
    )
    h = _expert_ffn(cfg, params, jnp.broadcast_to(x, (cfg.num_experts,) + x.shape))
    y = jnp.einsum("te,etd->td", combine, h)
    return y, aux


def moe_apply_sorted(cfg: ModelConfig, params: dict, x: jnp.ndarray, capacity_factor: float = 1.25):
    """Production path: sort + capacity-bucketed grouped matmul. x (T, d)."""
    T, d = x.shape
    k = cfg.experts_per_token
    E = cfg.num_experts
    C = capacity(cfg, T, capacity_factor)

    gates, top_idx, aux = route(cfg, params, x)             # (T,k)
    flat_e = top_idx.reshape(-1)                            # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)                   # token id per pair
    flat_g = gates.reshape(-1)

    order = jnp.argsort(flat_e)                             # stable sort by expert
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]

    counts = jax.ops.segment_sum(jnp.ones_like(se), se, num_segments=E)
    seg_start = jnp.cumsum(counts) - counts                 # (E,)
    rank = jnp.arange(T * k) - seg_start[se]                # rank within expert
    keep = rank < C                                         # capacity drop
    slot = jnp.where(keep, rank, C)                         # overflow -> slot C

    buf = jnp.zeros((E, C + 1, d), x.dtype)                 # +1 trash slot
    buf = buf.at[se, slot].set(x[st])
    out_buf = _expert_ffn(cfg, params, buf[:, :C])

    y_pairs = jnp.where(
        keep[:, None],
        out_buf[se, jnp.minimum(slot, C - 1)] * sg[:, None],
        0.0,
    )
    y = jax.ops.segment_sum(y_pairs, st, num_segments=T)
    return y, aux


def _held_ffn_plain(cfg: ModelConfig, x, top_idx, gates, w):
    """This device's part of the routed experts' output. x (T, d); top_idx,
    gates (T, k) over all ``num_experts``; w the held experts' (H, ., .)
    projections (``wi``, ``wg`` when gated, ``wo``)."""
    T, k = top_idx.shape
    H = cfg.resolved_experts_held()
    local = top_idx.reshape(-1) - cfg.expert_offset
    held = (local >= 0) & (local < H)
    group = jnp.where(held, local, H)                       # H: held elsewhere
    order = jnp.argsort(group, stable=True)                 # held pairs first, by expert
    tok = order // k
    sizes = jnp.bincount(group, length=H + 1)[:H].astype(jnp.int32)
    live = held[order][:, None]
    # rows past sum(sizes) belong to no held expert: ragged_dot leaves them
    # out of the product, and on a TPU leaves whatever memory held in its
    # output there. Every product's output is masked, and so is its input,
    # whose cotangent is the backward product's output.
    rows = jnp.where(live, x[tok], 0.0)                     # (T*k, d)

    def grouped(a, wt):
        return jnp.where(live, jax.lax.ragged_dot(a, wt, sizes), 0.0)

    h = grouped(rows, w[0])
    if cfg.act == "swiglu":
        h = jax.nn.silu(grouped(rows, w[1])) * h
    elif cfg.act == "geglu":
        h = jax.nn.gelu(grouped(rows, w[1]), approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    out = grouped(h, w[-1]) * gates.reshape(-1)[order].astype(x.dtype)[:, None]
    return jax.ops.segment_sum(out, tok, num_segments=T)


def _is_zero(t) -> bool:
    return isinstance(t, jax.custom_derivatives.SymbolicZero)


@functools.lru_cache(maxsize=None)
def _held_ffn(cfg: ModelConfig):
    """``_held_ffn_plain`` for ``cfg``, made batchable and differentiable.

    ``jax.lax.ragged_dot`` has no batching rule for weights shared across a
    batch, which is how DAG-FL validation evaluates several adapted models
    over one frozen base. Routing is per token, so a batch of token sets is
    one larger token set: the batching rule flattens the batch into the
    tokens and makes one grouped product. Differentiation goes through the
    plain function, in the inputs whose tangents are not symbolic zeros
    only, so a frozen expert's weights cost no tangent product.
    """
    plain = functools.partial(_held_ffn_plain, cfg)

    @custom_vmap
    def batched(x, top_idx, gates, w):
        return plain(x, top_idx, gates, w)

    @batched.def_vmap
    def _vmap(axis_size, in_batched, x, top_idx, gates, w):
        def lead(a, b):
            return a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)

        if any(jax.tree_util.tree_leaves(in_batched[3])):
            raise NotImplementedError("held experts batched over their weights")
        x, top_idx, gates = (lead(a, b) for a, b in zip((x, top_idx, gates), in_batched))

        def flat(a):
            return a.reshape((-1,) + a.shape[2:])

        y = batched(flat(x), flat(top_idx), flat(gates), w)
        return y.reshape(x.shape), True

    @jax.custom_jvp
    def ffn(x, top_idx, gates, w):
        return batched(x, top_idx, gates, w)

    def _jvp(primals, tangents):
        x, top_idx, gates, w = primals
        vals = {"x": x, "gates": gates, "w": w}
        tans = dict(zip(("x", "gates", "w"), (tangents[0], tangents[2], tangents[3])))
        live = [n for n, t in tans.items()
                if not all(map(_is_zero, jax.tree_util.tree_leaves(t, is_leaf=_is_zero)))]

        def f(*args):
            kw = dict(vals, **dict(zip(live, args)))
            return plain(kw["x"], top_idx, kw["gates"], kw["w"])

        def dense(t, p):
            return jnp.zeros_like(p) if _is_zero(t) else t

        if not live:
            y = f()
            return y, jnp.zeros_like(y)
        return jax.jvp(f, tuple(vals[n] for n in live),
                       tuple(jax.tree_util.tree_map(dense, tans[n], vals[n], is_leaf=_is_zero)
                             for n in live))

    ffn.defjvp(_jvp, symbolic_zeros=True)
    return ffn


def moe_apply_ragged(cfg: ModelConfig, params: dict, x: jnp.ndarray):
    """Dropless held-expert path (module docstring). x (T, d)."""
    with jax.named_scope("lm/moe/route"):
        gates, top_idx, aux = route(cfg, params, x)
    with jax.named_scope("lm/moe/experts"):
        w = tuple(params[n] for n in ("wi", "wg", "wo") if n in params)
        y = _held_ffn(cfg)(x, top_idx, gates, w)
    return y, aux


MOE_BLOCK_TOKENS = 32768


def moe_apply_blocked(cfg: ModelConfig, params: dict, x: jnp.ndarray,
                      block: int = MOE_BLOCK_TOKENS):
    """§Perf optimization: scan the sorted dispatch over token blocks.

    The (E, C, d) capacity buffer scales with the token count it serves; at
    train_4k kimi-scale (1M tokens, E=384, k=8) the global buffer is ~150 TB
    — GSPMD spills it as ~0.6 TB/device temp. Routing is per-token, so
    dispatching ``block`` tokens at a time is mathematically identical
    (same router, same capacity *rate*) while shrinking live buffers by
    T/block. Aux loss is averaged over blocks.
    """
    T = x.shape[0]
    if T <= block:
        return moe_apply_sorted(cfg, params, x)
    nb = -(-T // block)
    pad = nb * block - T
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    xb = xp.reshape(nb, block, -1)

    def body(_, xblk):
        y, aux = moe_apply_sorted(cfg, params, xblk)
        return None, (y, aux)

    _, (yb, auxb) = jax.lax.scan(body, None, xb)
    y = yb.reshape(nb * block, -1)[:T]
    return y, jnp.mean(auxb)


# mesh for the shard_map ("expert_parallel") dispatch; set by the launcher.
_SHARD_MAP_MESH = None


def set_shard_map_mesh(mesh) -> None:
    global _SHARD_MAP_MESH
    _SHARD_MAP_MESH = mesh


def moe_apply(cfg: ModelConfig, params: dict, x: jnp.ndarray, impl: str = "sorted"):
    """x: (..., d). Returns (y, aux)."""
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    if impl == "expert_parallel" and _SHARD_MAP_MESH is not None and len(shape) == 3:
        from repro.models.moe_shard_map import make_moe_shard_map

        y, aux = make_moe_shard_map(cfg, _SHARD_MAP_MESH)(params, x)
        y = y.reshape(-1, shape[-1])
    elif impl == "dense":
        y, aux = moe_apply_dense(cfg, params, flat)
    elif impl == "blocked":
        y, aux = moe_apply_blocked(cfg, params, flat)
    elif impl == "ragged":
        y, aux = moe_apply_ragged(cfg, params, flat)
    else:
        y, aux = moe_apply_sorted(cfg, params, flat)
    if cfg.num_shared_experts:
        import dataclasses

        shared_cfg = dataclasses.replace(
            cfg, d_ff=cfg.num_shared_experts * (cfg.moe_d_ff or cfg.d_ff)
        )
        with jax.named_scope("lm/moe/shared"):
            y = y + mlp_apply(shared_cfg, params["shared"], flat)
    return y.reshape(shape), aux
