"""Persistent XLA compilation cache for the launcher processes.

Compiling the quantizer's and the server's programs at published widths
takes minutes; a cache turns every later run of the same shapes into a
load.  :func:`enable_compile_cache` is called from the ``__main__`` blocks
of ``launch.quantize`` and ``launch.serve`` and from ``chip_smoke.py`` —
never at import or from ``main()``, so library users and the test suite
(and its worker processes) keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# One fixed path inside the checkout: the cache is keyed by the program,
# so a directory that moved between runs would never hit.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
    itself.  Otherwise the cache goes to ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
