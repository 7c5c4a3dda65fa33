"""The repo's Pallas kernel layer: every device-resident reduction the
overlay and the model stack lean on, each shipped as the same triple —
a Pallas kernel (compiled on TPU, interpreted elsewhere), a pure-lax
oracle in ``repro.kernels.ref`` (the allclose/bitwise ground truth and
the CPU fast path), and a dispatcher that picks per backend (``impl``
override for tests). Members:

* ``gossip_merge`` — per-row gossip-merge winner selection (+ the
  degree-compressed candidate-list variant);
* ``chunk_transfer`` — content-addressed chunk dedup, striped
  bandwidth-limited transfer selection, and receive-side digest
  verification for the priced bank;
* ``delta_codec`` — wire compression for bank commits: blocked int8/int4
  symmetric quantization and per-block top-k delta sparsification, plus
  the ``DeltaCodec`` pytree codec the engines price chunks with;
* ``event_pop`` — masked argmin pop for the continuous-time event queue;
* ``fedavg`` / ``model_distance`` — Eq. (1) aggregation and the pairwise
  parameter-space distances anomaly scoring uses;
* ``flash_attention`` / ``wkv`` — the model-side attention/recurrence
  kernels served from the gossiped bank.

``repro.kernels.ops`` re-exports jit'd wrappers; ``repro.kernels.dispatch``
holds the one rule that picks kernel or oracle, compiled or interpreted.
"""
from repro.kernels import ops, ref
from repro.kernels.delta_codec import DeltaCodec
from repro.kernels.ops import (
    chunk_dedup,
    decode_attention,
    event_pop,
    fedavg,
    flash_attention,
    gossip_winner,
    model_distance,
    quant_blocks,
    topk_blocks,
)

__all__ = [
    "ops",
    "ref",
    "chunk_dedup",
    "decode_attention",
    "event_pop",
    "fedavg",
    "flash_attention",
    "gossip_winner",
    "model_distance",
    "DeltaCodec",
    "quant_blocks",
    "topk_blocks",
]
