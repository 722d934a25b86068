"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
``use_compile_cache()`` first thing in their ``main``; nothing calls it
while a module is imported.  ``JAX_COMPILATION_CACHE_DIR``, when set, stays
in charge: JAX reads it itself and this sets nothing.  Otherwise the cache
lives at the fixed path ``<checkout>/.jax_cache``: the path is part of
what the cache is found by, so it must not depend on a temp name, a pid or
the time.
"""

from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
