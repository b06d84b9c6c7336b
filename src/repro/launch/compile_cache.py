"""Persistent compilation cache at one fixed path in the checkout.

JAX keys its cache on the directory too, so the path never comes from a
temp name, a pid or the time.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing is set here.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_checkout_cache() -> None:
    """Point JAX's compilation cache at ``<checkout>/.jax_cache`` unless the
    environment names one."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
