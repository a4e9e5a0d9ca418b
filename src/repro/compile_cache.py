"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`enable_compile_cache` once, before their first compile; library
modules never do.  ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache —
JAX reads it itself and this module sets nothing.  Otherwise the cache goes
to ``<repo>/.jax_cache`` (git-ignored): a fixed path, because the path is
part of every cache key, so a directory named after a process, a temporary
name or the time would never hit again.
"""

from __future__ import annotations

import os

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
