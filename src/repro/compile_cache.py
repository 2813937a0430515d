"""Where JAX keeps its persistent compilation cache.

Only entry points call :func:`configure_compile_cache` (``chip_smoke.py``,
``python -m repro``, ``benchmarks/run.py``); importing :mod:`repro`
configures nothing, and tests never call it.
"""

from __future__ import annotations

import os
import pathlib

# The cache key includes the directory, so the default is one fixed path at
# the checkout root: found from this file, not from the working directory.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone; otherwise the cache goes to ``.jax_cache/`` in the checkout.
    Call before the first compilation.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
