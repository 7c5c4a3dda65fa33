"""Pallas wire-compression kernels for the gossiped model bank.

The bank prices every chunk transfer at Table-I bandwidths
(``repro.net.bank.chunk_step``), and on the 1 Mbps constrained class raw
f32 chunks saturate the links — the communication-efficiency axis every
related DAG-FL system optimizes. This module is the codec layer that sits
between a committer and the wire: block-wise symmetric quantization
(int8 / int4, per-block scales) and top-k delta sparsification against
the receiver's last-held version of the same slot. Both are masked
reductions over fixed ``(num_blocks, block)`` shapes in the established
kernel/oracle/dispatch mold (``gossip_merge``, ``chunk_transfer``):

``quant_blocks``   per 128-element block: ``scale = amax / qmax`` (1.0 on
                   an all-zero block so padding round-trips exactly) and
                   ``codes = clip(round(x / scale), -qmax, qmax)``. The
                   Pallas kernel emits int32 codes (TPU-native lane type,
                   the ``chunk_dedup`` convention) cast to int8 outside;
                   int4 uses the same int8 carrier with ``qmax = 7`` and
                   is PRICED at two codes per byte by ``wire_ratio``.

``topk_blocks``    per block keep the k largest-|delta| elements, zero the
                   rest. Rank is the deterministic dense reduction
                   ``rank_i = #{j : |d_j| > |d_i| or (|d_j| = |d_i| and
                   j < i)}`` — no sort, no data-dependent shapes, ties
                   break toward the earlier index, and zeros never beat a
                   nonzero, so ``k >= nnz(block)`` reproduces the delta
                   exactly (property-tested).

``DeltaCodec``     the frozen (hashable — it rides the jit-factory cache
                   keys) pytree codec: ``encode(params, base)`` maps a
                   commit's payload to its wire form — a pytree whose
                   leaves are exactly the bytes that cross the link, so
                   ``bank.chunk_digests`` over it gives digests of the
                   ENCODED bytes and the PR-7 spoof defense verifies what
                   was actually transmitted — and ``decode(enc, base)``
                   inverts it against the receiver's last-held slot
                   content. ``wire_ratio()`` is the encoded/raw byte
                   ratio the engines use to price chunks
                   (``codec_key`` maps every ratio-1.0 codec to ``None``
                   so the identity path keeps the literal PR-7 programs).

Equivalence pallas-vs-ref, the round-trip error bound, and the
identity-codec bitwise property live in ``tests/test_delta_codec.py``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref
from repro.kernels.dispatch import interpret_mode, pick_impl

BLOCK = 128    # codec block length (lane-aligned: the f32 TPU tile is (8, 128))
BLOCK_T = 8    # block rows per pallas grid step

_QMAX = {"int8": 127, "int4": 7}


def _quant_kernel(x_ref, codes_ref, scale_ref, *, qmax):
    # x_ref: (bt, B) f32 — a slab of codec blocks
    # codes_ref: (bt, B) i32, scale_ref: (bt, 1) f32
    x = x_ref[...]
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0.0, amax / qmax, 1.0)
    codes_ref[...] = jnp.clip(
        jnp.round(x / scale), -qmax, qmax
    ).astype(jnp.int32)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("qmax", "block_t", "interpret"))
def quant_blocks_pallas(
    x: jnp.ndarray,          # (nb, B) f32 — one codec block per row
    qmax: int,
    block_t: int = BLOCK_T,
    interpret: bool = None,
) -> tuple:
    """Blocked symmetric quantization — the Pallas reduction.

    Grid step i quantizes a ``block_t``-row slab. Padding rows are zero,
    so their scale is exactly 1.0 and their codes 0 — sliced off outside.
    Returns ``(codes (nb, B) int8, scales (nb,) f32)``.
    """
    nb, b = x.shape
    bt = min(block_t, nb) if nb else block_t
    pad = (-nb) % bt
    xp = jnp.pad(jnp.asarray(x, jnp.float32), ((0, pad), (0, 0)))
    codes, scales = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=float(qmax)),
        grid=((nb + pad) // bt,),
        in_specs=[pl.BlockSpec((bt, b), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, b), lambda i: (i, 0)),
            pl.BlockSpec((bt, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb + pad, b), jnp.int32),
            jax.ShapeDtypeStruct((nb + pad, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(xp)
    return codes[:nb].astype(jnp.int8), scales[:nb, 0]


def _topk_kernel(d_ref, out_ref, *, k):
    # d_ref/out_ref: (bt, B) f32 — keep the k largest-|d| per row
    d = d_ref[...]
    a = jnp.abs(d)
    b = a.shape[-1]
    jj = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    ii = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    gt = a[:, :, None] > a[:, None, :]                    # [n, j, i]
    eq = (a[:, :, None] == a[:, None, :]) & (jj < ii)[None]
    rank = jnp.sum((gt | eq).astype(jnp.int32), axis=1)   # (bt, B)
    out_ref[...] = jnp.where(rank < k, d, 0.0)


@functools.partial(jax.jit, static_argnames=("k", "block_t", "interpret"))
def topk_blocks_pallas(
    d: jnp.ndarray,          # (nb, B) f32 — one delta block per row
    k: int,
    block_t: int = BLOCK_T,
    interpret: bool = None,
) -> jnp.ndarray:
    """Per-block top-k-|delta| masking — the Pallas reduction.

    The rank comparison materializes a ``(block_t, B, B)`` tensor, which
    is why ``block_t`` stays small. Returns the dense masked delta.
    """
    nb, b = d.shape
    bt = min(block_t, nb) if nb else block_t
    pad = (-nb) % bt
    dp = jnp.pad(jnp.asarray(d, jnp.float32), ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_topk_kernel, k=int(k)),
        grid=((nb + pad) // bt,),
        in_specs=[pl.BlockSpec((bt, b), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bt, b), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb + pad, b), jnp.float32),
        interpret=interpret_mode(interpret),
    )(dp)
    return out[:nb]


def quant_blocks(x, qmax: int, impl: str = None, block_t: int = BLOCK_T,
                 interpret: bool = None) -> tuple:
    """Blocked quantization with backend dispatch (the ``chunk_dedup`` rule).

    ``impl``: "pallas" forces the kernel, "lax" the pure-lax oracle; None
    follows ``repro.kernels.dispatch`` (pallas on TPU, lax elsewhere).
    """
    if pick_impl(impl, "quant_blocks") == "lax":
        return ref.quant_blocks_ref(x, qmax)
    return quant_blocks_pallas(x, qmax, block_t=block_t, interpret=interpret)


def topk_blocks(d, k: int, impl: str = None, block_t: int = BLOCK_T,
                interpret: bool = None) -> jnp.ndarray:
    """Per-block top-k masking with backend dispatch."""
    if pick_impl(impl, "topk_blocks") == "lax":
        return ref.topk_blocks_ref(d, k)
    return topk_blocks_pallas(d, k, block_t=block_t, interpret=interpret)


def _to_blocks(flat: jnp.ndarray, block: int) -> jnp.ndarray:
    """Zero-pad a flat vector up to whole codec blocks: (n,) -> (nb, block)."""
    n = flat.shape[0]
    nb = max(1, -(-n // block))
    pad = nb * block - n
    return jnp.pad(jnp.asarray(flat, jnp.float32), (0, pad)).reshape(nb, block)


@dataclass(frozen=True)
class DeltaCodec:
    """The wire codec for bank commits (frozen + hashable: it rides the
    ``lru_cache`` keys of the bank jit factories alongside obs/faults).

    ``kind`` — "none" (explicit identity: encode/decode are passthrough
    and the engines keep the literal uncompressed programs), "int8" /
    "int4" (blocked symmetric quantization; int4 codes travel two per
    byte, carried one-per-int8 in simulation), or "topk" (per-block
    top-k delta vs the receiver's last-held slot content);
    ``block`` — codec block length (per-block scale / top-k granularity);
    ``topk_frac`` — fraction of each block kept by "topk";
    ``impl`` — kernel dispatch override ("pallas"/"lax"/None), same
    semantics as ``BankGossipConfig.impl``.
    """

    kind: str = "int8"
    block: int = BLOCK
    topk_frac: float = 0.0625
    impl: Optional[str] = None

    def __post_init__(self):
        if self.kind not in ("none", "int8", "int4", "topk"):
            raise ValueError(f"unknown codec kind: {self.kind!r}")

    @property
    def is_identity(self) -> bool:
        return self.kind == "none"

    def topk_k(self) -> int:
        """Elements kept per block by the "topk" kind (at least 1)."""
        return max(1, int(round(self.topk_frac * self.block)))

    def wire_ratio(self) -> float:
        """Encoded / raw wire bytes per chunk — the pricing the engines
        fold into ``chunk_bytes``.

        Raw: 4 bytes per f32 element. int8: one code byte per element
        plus a 4-byte f32 scale per block. int4: half a code byte per
        element plus the scale. topk: 8 bytes (4-byte index + 4-byte
        value) per kept element — the sparse framing the dense masked
        array stands in for.
        """
        if self.kind == "none":
            return 1.0
        if self.kind == "int8":
            return (self.block + 4.0) / (4.0 * self.block)
        if self.kind == "int4":
            return (self.block / 2.0 + 4.0) / (4.0 * self.block)
        return min(1.0, 8.0 * self.topk_k() / (4.0 * self.block))

    def encode(self, params, base):
        """Payload pytree -> wire pytree.

        The wire pytree's leaves are exactly what crosses the link, so
        digesting it (``bank.chunk_digests`` flattens leaves) digests the
        ENCODED bytes. ``base`` is the receiver's last-held content of
        the same slot ("topk" encodes the delta against it; quant kinds
        ignore it — their encoding is base-free, which is what keeps
        content-addressed dedup of identical payloads alive).
        """
        if self.kind == "none":
            return params
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if self.kind in ("int8", "int4"):
            qmax = _QMAX[self.kind]
            enc = [
                quant_blocks(_to_blocks(jnp.ravel(l), self.block), qmax,
                             impl=self.impl)
                for l in leaves
            ]
            return {
                "codes": jax.tree_util.tree_unflatten(
                    treedef, [c for c, _ in enc]),
                "scales": jax.tree_util.tree_unflatten(
                    treedef, [s for _, s in enc]),
            }
        k = self.topk_k()
        base_leaves = jax.tree_util.tree_leaves(base)
        deltas = [
            topk_blocks(
                _to_blocks(
                    jnp.ravel(l).astype(jnp.float32)
                    - jnp.ravel(b).astype(jnp.float32),
                    self.block,
                ),
                k, impl=self.impl,
            )
            for l, b in zip(leaves, base_leaves)
        ]
        return {"delta": jax.tree_util.tree_unflatten(treedef, deltas)}

    def decode(self, enc, base):
        """Wire pytree -> payload pytree (shape/dtype of ``base``)."""
        if self.kind == "none":
            return enc

        def _restore(flat, b):
            return flat[: b.size].reshape(b.shape)

        if self.kind in ("int8", "int4"):
            return jax.tree_util.tree_map(
                lambda c, s, b: _restore(
                    jnp.ravel(c.astype(jnp.float32) * s[:, None]), b
                ).astype(b.dtype),
                enc["codes"], enc["scales"], base,
            )
        return jax.tree_util.tree_map(
            lambda d, b: (
                b.astype(jnp.float32) + _restore(jnp.ravel(d), b)
            ).astype(b.dtype),
            enc["delta"], base,
        )


def codec_key(codec: Optional[DeltaCodec]) -> Optional[DeltaCodec]:
    """The static codec key the engines hand their jit factories.

    Every codec that prices like raw bytes (``None``, kind "none", or a
    degenerate ratio-1.0 configuration) maps to ``None``, so the factories
    keep the LITERAL uncompressed program — multiplying ``chunk_bytes``
    by 1.0 would change the XLA graph and break the bitwise-identity
    contract the identity-codec tests pin.
    """
    if codec is None or codec.wire_ratio() == 1.0:
        return None
    return codec
