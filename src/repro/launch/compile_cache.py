"""JAX's persistent compilation cache for this checkout's entry points.

``chip_smoke.py``, ``launch/serve.py``, ``examples/train_fno.py`` and
the on-chip benchmark's harness (``benchmarks/onchip/harness.py``) call
:func:`enable_compile_cache` before their first compile, so a second run on the same machine finds the compiled kernels
again instead of recompiling them.
"""
from __future__ import annotations

import os

import jax

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and
    wins: nothing is set here then. Otherwise the cache lives in the fixed
    ``.jax_cache/`` at the checkout root (gitignored). The directory is
    part of where an entry is looked up, so it never depends on a temp
    name, a pid or the time."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
