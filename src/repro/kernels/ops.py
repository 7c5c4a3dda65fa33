"""Jit'd public wrappers for the Pallas kernels.

How each call runs (compiled kernel, interpreted kernel, or lax oracle) is
decided by ``repro.kernels.dispatch``: compiled Pallas on TPU.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.chunk_transfer import chunk_dedup, transfer_select
from repro.kernels.delta_codec import DeltaCodec, quant_blocks, topk_blocks
from repro.kernels.event_pop import event_pop
from repro.kernels.fedavg import fedavg_pallas
from repro.kernels.flash_attention import decode_attention_pallas, flash_attention_pallas
from repro.kernels.gossip_merge import gossip_winner, gossip_winner_nbr
from repro.kernels.hist_bincount import hist_bincount_pallas
from repro.kernels.model_distance import model_distance_pallas
from repro.kernels.wkv import wkv_pallas
from repro.kernels import ref
from repro.kernels.dispatch import pick_impl


def fedavg(weights: jnp.ndarray, models: jnp.ndarray, block_n: int = 16384) -> jnp.ndarray:
    """Eq. (1) weighted model average. weights (k,), models (k, N) -> (N,)."""
    return fedavg_pallas(weights, models, block_n=block_n)


def model_distance(models: jnp.ndarray, block_n: int = 16384) -> jnp.ndarray:
    """Pairwise squared-L2 distances (k, N) -> (k, k)."""
    return model_distance_pallas(models, block_n=block_n)


def flash_attention(q, k, v, window: int = 0, block_q: int = 128, block_k: int = 128):
    """Causal (optionally sliding-window) GQA attention (B,H,S,hd)."""
    return flash_attention_pallas(
        q, k, v, window=window, block_q=block_q, block_k=block_k,
    )


def decode_attention(q, k, v, lengths, block_s: int = 512):
    """Single-token GQA decode attention against an S-slot cache."""
    return decode_attention_pallas(q, k, v, lengths, block_s=block_s)


def wkv(r, k, v, logw, u, chunk: int = 32):
    """Chunk-parallel RWKV6 WKV recurrence (B,T,H,hd)."""
    return wkv_pallas(r, k, v, logw, u, chunk=chunk)


def hist_bincount(idx, weights, num_bins: int, impl: str = None,
                  block_m: int = 512):
    """Weighted bincount for the streaming histograms (m,) -> (num_bins,).

    ``impl``: None follows ``repro.kernels.dispatch`` (pallas on TPU, the
    pure-lax scatter-add oracle elsewhere — in-loop histogram updates stay
    cheap on CPU hosts).
    """
    if pick_impl(impl, "hist_bincount") == "lax":
        return ref.hist_bincount_ref(idx, weights, num_bins)
    return hist_bincount_pallas(idx, weights, num_bins, block_m=block_m)


__all__ = [
    "fedavg", "model_distance", "flash_attention", "decode_attention", "wkv",
    "gossip_winner", "gossip_winner_nbr", "chunk_dedup", "transfer_select",
    "event_pop", "hist_bincount", "DeltaCodec", "quant_blocks",
    "topk_blocks", "ref",
]
