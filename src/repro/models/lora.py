"""Low-rank adapters (LoRA, arXiv:2106.09685) over a frozen base model.

A projection ``y = x @ W`` gains ``y += (alpha / rank) * (x @ A) @ B``, with
``A`` (fan_in, rank) drawn from a seed and ``B`` (rank, fan_out) zero, so a
fresh adapter leaves the base's output unchanged. Only ``A`` and ``B``
train; the base stays frozen and shared.

Adapters are a pytree in the base's own layout: one ``{"a", "b"}`` pair per
targeted weight, under the keys of the block that holds the weight (the
``prefix`` list and the stacked ``layers`` of ``models/transformer.py``).
``merge`` slots each pair in beside its weight as an ``Adapter`` under the
block's ``"lora"`` key, which ``models/mla.py`` applies.

Seeding, of adapters and of a random frozen base alike: every leaf draws
from its own key, ``fold_in(key, crc32(path))``, where ``path`` is the
leaf's ``/``-joined position in the tree (``layers/attn/wq/a``), so a leaf's
values do not depend on which other leaves exist.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Any, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    rank: int = 16
    alpha: float = 32.0
    # the MLA projections of DeepSeek-V2 (HF names q_proj, kv_a_proj_with_mqa,
    # kv_b_proj, o_proj), under their names in ``models/mla.py``
    targets: Tuple[str, ...] = ("wq", "wkv_a", "wkv_b", "wo")

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


@functools.partial(jax.tree_util.register_dataclass, data_fields=["a", "b"],
                   meta_fields=["scale"])
@dataclasses.dataclass(frozen=True)
class Adapter:
    a: Any
    b: Any
    scale: float


def delta(ad: Adapter, x: jnp.ndarray) -> jnp.ndarray:
    """The adapter's addition to ``x @ W``."""
    with jax.named_scope("lm/lora"):
        return ((x @ ad.a) @ ad.b) * ad.scale


def path_name(path) -> str:
    """``/``-joined dict keys and list indices of a tree path."""
    parts = []
    for p in path:
        parts.append(str(p.key if hasattr(p, "key") else p.idx))
    return "/".join(parts)


def leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()))


def random_base(key, shapes) -> Any:
    """A frozen base of random weights for the tree of ``shapes``
    (``jax.eval_shape`` of a model's init): norm scales one, embeddings
    N(0, 1), every other weight N(0, 1/fan_in) with fan_in its second-last
    dimension; one key per leaf (module docstring)."""

    def draw(path, s):
        name = path_name(path)
        if name.endswith("scale"):
            return jnp.ones(s.shape, s.dtype)
        w = jax.random.normal(leaf_key(key, name), s.shape, jnp.float32)
        if name != "embed":
            w = w * (1.0 / math.sqrt(s.shape[-2]))
        return w.astype(s.dtype)

    return jax.tree_util.tree_map_with_path(draw, shapes)


def init_adapters(key, shapes, lora: LoRAConfig) -> Any:
    """Adapters for every targeted attention weight of ``shapes``: ``a``
    N(0, 1/fan_in) from the leaf's key, ``b`` zero."""

    def walk(node, prefix):
        if isinstance(node, (list, tuple)):
            return [walk(v, prefix + (str(i),)) for i, v in enumerate(node)]
        out = {}
        for k, v in node.items():
            if isinstance(v, (dict, list, tuple)):
                sub = walk(v, prefix + (k,))
                if sub:
                    out[k] = sub
            elif k in lora.targets and "attn" in prefix:
                lead, (fan_in, fan_out) = v.shape[:-2], v.shape[-2:]
                name = "/".join(prefix + (k, "a"))
                a = jax.random.normal(leaf_key(key, name), lead + (fan_in, lora.rank),
                                      jnp.float32) / math.sqrt(fan_in)
                out[k] = {"a": a, "b": jnp.zeros(lead + (lora.rank, fan_out), jnp.float32)}
        return out

    return walk({k: shapes[k] for k in ("prefix", "layers") if k in shapes}, ())


def merge(base, adapters, lora: LoRAConfig):
    """``base`` with each adapter pair set beside its weight, as an
    ``Adapter`` under the holding block's ``"lora"`` key."""

    def walk(b, a):
        if isinstance(a, list):
            return [walk(bi, ai) for bi, ai in zip(b, a)]
        out = dict(b)
        for k, v in a.items():
            if isinstance(v, dict) and set(v) == {"a", "b"}:
                out["lora"] = dict(out.get("lora", {}), **{k: Adapter(v["a"], v["b"],
                                                                      lora.scale)})
            else:
                out[k] = walk(b[k], v)
        return out

    return walk(base, adapters)
