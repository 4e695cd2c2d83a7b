"""Where JAX's persistent compilation cache lives.

Called by entry points (``chip_smoke.py``, the train/serve/dryrun CLIs, the
benchmark scripts) before their first compile, never at library import.
"""
from __future__ import annotations

import os

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def setup_compile_cache() -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself,
    so nothing else is set); otherwise the fixed ``<checkout>/.jax_cache``.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
