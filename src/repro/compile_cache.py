"""Where JAX keeps its persistent compilation cache for this checkout.

At n = 28 a cold compile of one stage program takes seconds to minutes, so
every entry point (``launch.simulate``, ``launch.serve_sim``,
``chip_smoke.py``) calls :func:`enable_compile_cache`
before its first compile. The directory is ``$JAX_COMPILATION_CACHE_DIR``
when that is set (no other cache is configured then), otherwise the fixed
``<checkout>/.jax_cache``. The path never depends on a temp name, a pid or
the time, so a later run finds what an earlier one stored.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point ``jax_compilation_cache_dir`` at the cache; returns the path."""
    import jax

    path = os.environ.get(ENV) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
