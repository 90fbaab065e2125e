"""JAX's persistent compilation cache, kept at one fixed path.

The path is part of what the cache is keyed on, so a directory that moves
from run to run never hits.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing here overrides it; otherwise the cache
lives in ``.jax_cache`` at the root of the checkout (ignored by git).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: <checkout>/.jax_cache -- this file is <checkout>/src/repro/launch/.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
