"""Where JAX keeps its persistent compilation cache.

``enable_compile_cache()`` is called by the entry points (``chip_smoke.py``,
``repro.launch.train``, ``benchmarks.run``), never while a module is
imported. If ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the
cache and no other is set. Otherwise the cache lives at one fixed path in
the checkout, ``<repo>/.jax_cache`` (ignored by git): the path is part of
each entry's key, so a cache that moved between runs would never hit.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory."""
    import jax

    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
