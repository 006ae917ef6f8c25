"""JAX persistent compilation cache for the entry points.

Called from the ``main()`` of ``chip_smoke.py``, ``launch/serve.py`` and
``launch/train.py``, never at import.  ``JAX_COMPILATION_CACHE_DIR``, when
set, is what JAX reads on its own and is left as it is; otherwise the cache
sits at a fixed ``<checkout>/.jax_cache`` so that every run of the same
checkout finds the programs an earlier run compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
