"""Where JAX keeps its persistent compilation cache, for entry points.

Call :func:`use_compile_cache` from a program's ``main`` (``chip_smoke.py``,
``benchmarks/run.py``), never while a module is imported and never from the
tests.  ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads
it itself; otherwise the cache lives at a fixed path inside the checkout,
because the path is part of what makes a later run find it.
"""
from __future__ import annotations

import os

#: the checkout's own cache directory (listed in ``.gitignore``).
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on for every compile, however short (many
    device steps compile in under JAX's one-second default), and return
    its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
