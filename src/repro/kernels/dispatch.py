"""How a kernel call runs: the one backend rule every dispatcher shares.

On a TPU a dispatcher picks its Pallas kernel and the kernel runs compiled
(``interpret=False``). Elsewhere a dispatcher picks the pure-lax oracle in
``repro.kernels.ref`` (the Pallas interpreter's per-grid-step loop is slower
than one fused lax reduction on a CPU), and a Pallas kernel asked for
explicitly runs in the interpreter. An explicit ``impl`` or ``interpret``
argument always wins over the rule.
"""
from __future__ import annotations

from typing import Optional

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pick_impl(impl: Optional[str], name: str) -> str:
    """"pallas" or "lax" for a dispatcher's ``impl`` (None: the backend rule)."""
    if impl is None:
        return "pallas" if on_tpu() else "lax"
    if impl not in ("pallas", "lax"):
        raise ValueError(f"unknown {name} impl: {impl!r}")
    return impl


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """A Pallas call's ``interpret`` flag (None: compiled exactly on TPU)."""
    return not on_tpu() if interpret is None else bool(interpret)
