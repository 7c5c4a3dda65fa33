"""Unified decoder-only model covering every assigned family.

families:
  dense / audio / vlm  — transformer blocks (GQA/MQA/MLA attention + MLP)
  moe                  — transformer blocks with routed-expert FFN
  rwkv                 — RWKV6 blocks (attention-free)
  hybrid               — Mamba2 blocks + ONE shared attention block every k

Layer stacks are homogeneous and scanned (``lax.scan`` over stacked params)
so 61-layer/1T-param graphs stay compact for the dry-run compiler; DeepSeek's
leading dense layer lives in an unscanned prefix. ``audio``/``vlm`` accept
stubbed frontend embeddings (precomputed frames/patches per the assignment)
that a learned projector prepends to the token sequence.

Everything is functional: ``forward(params, tokens)`` vmaps over a leading
params axis, which is exactly how DAG-FL tip validation evaluates a bank of
candidate models in one XLA program.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models.attention import KVCache
from repro.models.layers import (
    dense_init,
    embed_init,
    mlp_apply,
    mlp_init,
    norm_apply,
    norm_init,
    softmax_xent,
)

# ---------------------------------------------------------------------------
# per-block init / apply for transformer-ish families
# ---------------------------------------------------------------------------


def _tf_block_init(key, cfg: ModelConfig, dense_mlp: bool, dtype) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": norm_init(cfg.norm, cfg.d_model, dtype),
        "ln2": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if cfg.attention == "mla":
        p["attn"] = mla_lib.mla_init(k1, cfg, dtype)
    else:
        p["attn"] = attn_lib.attn_init(k1, cfg, dtype)
    if cfg.is_moe() and not dense_mlp:
        p["moe"] = moe_lib.moe_init(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_init(k3, cfg, dtype=dtype)
    return p


def _tf_block_apply(
    cfg: ModelConfig,
    p: dict,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    mode: str,                     # "train" | "prefill" | "decode"
    cache,                         # layer cache or None
    cache_len: int,
    dense_mlp: bool,
):
    h = norm_apply(cfg.norm, p["ln1"], x, cfg.rms_norm_eps)
    new_cache = None
    if cfg.attention == "mla":
        if mode == "decode":
            a, new_cache = mla_lib.mla_decode_step(cfg, p["attn"], h, cache)
        else:
            a, new_cache = mla_lib.mla_forward(
                cfg, p["attn"], h, positions,
                return_cache=(mode == "prefill"), cache_len=cache_len,
            )
    else:
        if mode == "decode":
            a, new_cache = attn_lib.attn_decode_step(cfg, p["attn"], h, cache)
        else:
            a, new_cache = attn_lib.attn_forward(
                cfg,
                p["attn"],
                h,
                positions,
                return_cache=(mode == "prefill"),
                cache_len=cache_len,
            )
    x = x + a
    h = norm_apply(cfg.norm, p["ln2"], x, cfg.rms_norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        m, aux = moe_lib.moe_apply(cfg, p["moe"], h, impl=cfg.moe_impl)
    else:
        with jax.named_scope("lm/mlp"):
            m = mlp_apply(cfg, p["mlp"], h)
    return x + m, new_cache, aux


# ---------------------------------------------------------------------------
# hybrid (Zamba2) blocks
# ---------------------------------------------------------------------------


def _shared_attn_init(key, cfg: ModelConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": norm_init(cfg.norm, cfg.d_model, dtype),
        "attn": attn_lib.attn_init(k1, cfg, dtype),
        "ln2": norm_init(cfg.norm, cfg.d_model, dtype),
        "mlp": mlp_init(k2, cfg, dtype=dtype),
    }


def _shared_attn_apply(cfg, p, x, positions, mode, cache, cache_len):
    h = norm_apply(cfg.norm, p["ln1"], x)
    if mode == "decode":
        a, new_cache = attn_lib.attn_decode_step(cfg, p["attn"], h, cache)
    else:
        a, new_cache = attn_lib.attn_forward(
            cfg, p["attn"], h, positions, return_cache=(mode == "prefill"), cache_len=cache_len
        )
    x = x + a
    h = norm_apply(cfg.norm, p["ln2"], x)
    return x + mlp_apply(cfg, p["mlp"], h), new_cache


def _hybrid_layer_init(key, cfg: ModelConfig, dtype) -> dict:
    return {
        "ln": norm_init(cfg.norm, cfg.d_model, dtype),
        "mixer": mamba_lib.mamba_init(key, cfg, dtype),
    }


# ---------------------------------------------------------------------------
# the Model
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    # rematerialise each layer in the backward pass of "train" mode; off
    # where activations are small and recomputing them is pure cost
    remat: bool = True

    # ---------------- init ------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
            "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
        if cfg.frontend_tokens:
            params["frontend_proj"] = dense_init(keys[2], cfg.frontend_dim, cfg.d_model, dtype)

        n_stack = cfg.num_layers - cfg.first_dense_layers
        if cfg.family == "rwkv":
            lkeys = jax.random.split(keys[3], cfg.num_layers)
            params["layers"] = jax.vmap(lambda k: rwkv_lib.rwkv_block_init(k, cfg, dtype))(lkeys)
            params["embed_norm"] = norm_init("layernorm", cfg.d_model, dtype)
        elif cfg.family == "hybrid":
            lkeys = jax.random.split(keys[3], cfg.num_layers)
            params["layers"] = jax.vmap(lambda k: _hybrid_layer_init(k, cfg, dtype))(lkeys)
            params["shared_attn"] = _shared_attn_init(keys[4], cfg, dtype)
        else:
            if cfg.first_dense_layers:
                pkeys = jax.random.split(keys[5], cfg.first_dense_layers)
                params["prefix"] = [
                    _tf_block_init(pk, cfg, dense_mlp=True, dtype=dtype) for pk in pkeys
                ]
            lkeys = jax.random.split(keys[3], n_stack)
            params["layers"] = jax.vmap(
                lambda k: _tf_block_init(k, cfg, dense_mlp=False, dtype=dtype)
            )(lkeys)
        return params

    # ---------------- embeddings / head -----------------------------------
    def _embed(self, params, tokens, frontend):
        x = params["embed"][tokens]
        if self.cfg.frontend_tokens:
            assert frontend is not None, "audio/vlm archs need frontend embeddings"
            fe = frontend.astype(x.dtype) @ params["frontend_proj"]
            x = jnp.concatenate([fe, x], axis=1)
        return x

    def _head(self, params, x):
        with jax.named_scope("lm/head"):
            x = norm_apply(self.cfg.norm, params["final_norm"], x, self.cfg.rms_norm_eps)
            if self.cfg.tie_embeddings:
                return x @ params["embed"].T
            return x @ params["lm_head"]

    # ---------------- full-sequence passes ---------------------------------
    def _run_layers(self, params, x, positions, mode: str, cache, cache_len: int):
        """Dispatch per family; returns (x, new_cache, aux)."""
        cfg = self.cfg
        if cfg.family == "rwkv":
            return self._run_rwkv(params, x, mode, cache)
        if cfg.family == "hybrid":
            return self._run_hybrid(params, x, positions, mode, cache, cache_len)
        return self._run_tf(params, x, positions, mode, cache, cache_len)

    # -- transformer / moe stack
    def _run_tf(self, params, x, positions, mode, cache, cache_len):
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        new_prefix = []
        for i in range(cfg.first_dense_layers):
            c = cache["prefix"][i] if cache is not None else None
            x, nc, aux = _tf_block_apply(
                cfg, params["prefix"][i], x, positions, mode, c, cache_len, dense_mlp=True
            )
            new_prefix.append(nc)
            aux_total = aux_total + aux

        if mode == "decode":
            def body(carry, xs):
                h, auxs = carry
                lp, lc = xs
                h, nc, aux = _tf_block_apply(cfg, lp, h, positions, mode, lc, cache_len, False)
                return (h, auxs + aux), nc

            (x, aux_total), new_stack = jax.lax.scan(
                body, (x, aux_total), (params["layers"], cache["stack"])
            )
        else:
            def body(carry, lp):
                h, auxs = carry
                h, nc, aux = _tf_block_apply(cfg, lp, h, positions, mode, None, cache_len, False)
                return (h, auxs + aux), nc

            if mode == "train" and self.remat:
                body = jax.checkpoint(body, prevent_cse=False)
            (x, aux_total), new_stack = jax.lax.scan(body, (x, aux_total), params["layers"])

        new_cache = None
        if mode in ("prefill", "decode"):
            new_cache = {"prefix": new_prefix, "stack": new_stack}
        return x, new_cache, aux_total

    # -- rwkv stack
    def _run_rwkv(self, params, x, mode, states):
        cfg = self.cfg
        x = norm_apply("layernorm", params["embed_norm"], x)

        def body(h, xs):
            lp, st = xs
            h, new_st = rwkv_lib.rwkv_block_apply(cfg, lp, h, st)
            return h, new_st

        if mode == "train":
            body = jax.checkpoint(body, prevent_cse=False)
        if states is None:
            B = x.shape[0]
            states = self._rwkv_states(B, stacked=True)
        x, new_states = jax.lax.scan(body, x, (params["layers"], states))
        new_cache = new_states if mode in ("prefill", "decode") else None
        return x, new_cache, jnp.zeros((), jnp.float32)

    def _rwkv_states(self, batch, stacked=True):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        st = rwkv_lib.rwkv_empty_state(cfg, batch, dtype)
        if stacked:
            st = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape), st
            )
        return st

    # -- hybrid (mamba + shared attention) stack
    def _run_hybrid(self, params, x, positions, mode, cache, cache_len):
        cfg = self.cfg
        every = cfg.shared_attn_every
        n_apps = cfg.num_layers // every if every else 0
        B = x.shape[0]
        dtype = jnp.dtype(cfg.dtype)

        if cache is None:
            mstates = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape),
                mamba_lib.mamba_empty_state(cfg, B, dtype),
            )
            acaches = None
        else:
            mstates, acaches = cache["mamba"], cache["attn"]

        if acaches is None and mode != "train" and n_apps:
            slots = cache_len or x.shape[1]
            one = attn_lib.empty_cache(cfg, B, slots, dtype)
            acaches = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (n_apps,) + a.shape), one
            )

        shared = params["shared_attn"]

        def body(carry, xs):
            h, ac = carry
            idx, lp, mst = xs
            h2, new_mst = mamba_lib.mamba_apply(
                cfg, lp["mixer"], norm_apply(cfg.norm, lp["ln"], h), mst
            )
            h = h + h2
            if every:
                def with_attn(h, ac):
                    app = idx // every
                    if mode == "train":
                        h2, _ = _shared_attn_apply(cfg, shared, h, positions, mode, None, cache_len)
                        return h2, ac
                    layer_cache = jax.tree_util.tree_map(lambda a: a[app], ac)
                    h2, nc = _shared_attn_apply(
                        cfg, shared, h, positions, mode, layer_cache, cache_len
                    )
                    ac = jax.tree_util.tree_map(
                        lambda a, n: jax.lax.dynamic_update_index_in_dim(a, n, app, 0),
                        ac,
                        nc,
                    )
                    return h2, ac

                apply_attn = (idx % every) == (every - 1)
                h, ac = jax.lax.cond(apply_attn, with_attn, lambda h, ac: (h, ac), h, ac)
            return (h, ac), new_mst

        if mode == "train":
            body = jax.checkpoint(body, prevent_cse=False)
        idxs = jnp.arange(cfg.num_layers)
        if acaches is None:  # train mode placeholder so cond branches match
            acaches = jnp.zeros((), jnp.float32)
        (x, acaches), new_mstates = jax.lax.scan(
            body, (x, acaches), (idxs, params["layers"], mstates)
        )
        new_cache = None
        if mode in ("prefill", "decode"):
            new_cache = {"mamba": new_mstates, "attn": acaches}
        return x, new_cache, jnp.zeros((), jnp.float32)

    # ---------------- public API -------------------------------------------
    def forward(self, params, tokens, frontend=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Full-sequence logits (train path). Returns (logits, aux_loss)."""
        x = self._embed(params, tokens, frontend)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        x, _, aux = self._run_layers(params, x, positions, "train", None, 0)
        return self._head(params, x), aux

    def prefill(self, params, tokens, frontend=None, cache_len: int = 0):
        """Build the serving cache; returns (last-position logits, cache)."""
        x = self._embed(params, tokens, frontend)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        x, cache, _ = self._run_layers(params, x, positions, "prefill", None, cache_len or S)
        return self._head(params, x[:, -1:, :]), cache

    def decode_step(self, params, token, cache):
        """token: (B, 1) int32. Returns (logits (B,1,V), new cache)."""
        x = params["embed"][token]
        positions = None  # per-layer caches carry their own positions
        x, new_cache, _ = self._run_layers(params, x, positions, "decode", cache, 0)
        return self._head(params, x), new_cache

    def init_cache(self, batch: int, max_len: int, length: int = 0):
        """Cache stand-in for decode; ``length`` tokens considered present."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        if cfg.family == "rwkv":
            return self._rwkv_states(batch, stacked=True)
        if cfg.family == "hybrid":
            n_apps = cfg.num_layers // cfg.shared_attn_every
            one = attn_lib.empty_cache(cfg, batch, max_len, dtype, length)
            return {
                "mamba": jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (cfg.num_layers,) + a.shape),
                    mamba_lib.mamba_empty_state(cfg, batch, dtype),
                ),
                "attn": jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (n_apps,) + a.shape), one
                ),
            }
        n_stack = cfg.num_layers - cfg.first_dense_layers
        if cfg.attention == "mla":
            one = mla_lib.mla_empty_cache(cfg, batch, max_len, dtype, length)
        else:
            one = attn_lib.empty_cache(cfg, batch, max_len, dtype, length)
        stack = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (n_stack,) + a.shape), one)
        prefix = [
            jax.tree_util.tree_map(lambda a: a, one) for _ in range(cfg.first_dense_layers)
        ]
        return {"prefix": prefix, "stack": stack}

    # ---------------- training --------------------------------------------
    def loss(self, params, batch) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        tokens = batch["tokens"]
        labels = batch["labels"]
        frontend = batch.get("frontend")
        logits, aux = self.forward(params, tokens, frontend)
        F = self.cfg.frontend_tokens
        if F:
            # position F-1+j predicts text token j
            logits = logits[:, F - 1 : F - 1 + tokens.shape[1], :]
            labels = tokens
        else:
            logits = logits[:, :-1, :]
            labels = labels[:, 1:]
        xent = softmax_xent(logits, labels)
        total = xent + self.cfg.router_aux_loss * aux
        return total, {"xent": xent, "aux": aux}

    def train_step(self, train_cfg, params, opt_state, batch, lr):
        from repro.optim import make_optimizer

        _, update = make_optimizer(train_cfg)

        def loss_fn(p):
            total, metrics = self.loss(p, batch)
            return total, metrics

        (total, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state = update(grads, opt_state, params, lr)
        metrics = dict(metrics, loss=total)
        return params, opt_state, metrics


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
