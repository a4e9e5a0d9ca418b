"""JAX's persistent compilation cache, and a monitor of compiles and cache
traffic.

The cache lives in ``$JAX_COMPILATION_CACHE_DIR`` where that is set, and
otherwise at the fixed path ``chipbench/.jax_cache`` inside the checkout
(git-ignored; a fixed path, since the path is part of every cache key).
JAX caches only programs that took at least
``jax_persistent_cache_min_compile_time_secs`` (1 s by default) to compile;
the benchmark lowers that floor to 0 and lifts the entry-size floor, so
every program of a run, kernels and small eager operations included, is
found again by the next run.
"""

from __future__ import annotations

import functools
import os

DEFAULT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache")

_COMPILE = "/jax/core/compile/backend_compile_duration"
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR


def enable() -> str:
    import jax

    path = cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Monitor:
    """Counts backend compiles (with their seconds), traces, and persistent
    cache hits and misses, from JAX's own monitoring events."""

    def __init__(self) -> None:
        self.counts = {"compiles": 0, "compile_s": 0.0, "traces": 0, "hits": 0, "misses": 0}
        self._mark = dict(self.counts)

    def on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _COMPILE:
            self.counts["compiles"] += 1
            self.counts["compile_s"] += duration
        elif event == _TRACE:
            self.counts["traces"] += 1

    def on_event(self, event: str, **kw) -> None:
        if event == _HIT:
            self.counts["hits"] += 1
        elif event == _MISS:
            self.counts["misses"] += 1

    def mark(self) -> None:
        self._mark = dict(self.counts)

    def since_mark(self) -> dict:
        return {k: self.counts[k] - self._mark[k] for k in self.counts}


@functools.cache
def monitor() -> Monitor:
    """The process's monitor (JAX's listeners are process-wide)."""
    import jax.monitoring

    m = Monitor()
    jax.monitoring.register_event_duration_secs_listener(m.on_duration)
    jax.monitoring.register_event_listener(m.on_event)
    return m


def entries() -> int:
    path = cache_dir()
    return len(os.listdir(path)) if os.path.isdir(path) else 0
