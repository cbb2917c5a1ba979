"""Where JAX's persistent compilation cache lives for this checkout's runs.

Entry points (``serve_truss.main``, ``chip_smoke.py``) call
``configure_compile_cache()`` once at start-up; nothing calls it at import.
``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it itself.
Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed path,
because the path is part of what makes a cached program hit again.
"""
from __future__ import annotations

import os

import jax

#: the checkout root (``src/repro/launch/`` is three levels below it)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point the cache at ``$JAX_COMPILATION_CACHE_DIR`` or the checkout's
    ``.jax_cache``; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
