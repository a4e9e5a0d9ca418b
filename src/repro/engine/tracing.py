"""Named host spans inside the engine, on the profiler's own clock.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` once the jit tier is
up (:func:`enable`), so a profiler trace holds the engine's host work on the
same clock as the device's operations and every idle gap of the device can
be put down to the innermost span the host was in.  Before that, and on the
numpy tiers, which import no jax, it is one shared no-op context.  Without
an active profiler session an annotation costs about a microsecond, so the
spans stay on: a few tens per tick.

Names are fixed strings (annotation keyword arguments would be encoded into
the event name and break matching by name); :data:`SPANS` and
:data:`EXTRA_SPANS` list them all.
Nesting on the engine's one Python thread gives each span its parent.
"""

from __future__ import annotations

import contextlib
import functools
import gc

#: Every span the engine records, in the order of a tick and a period.
SPANS = (
    "engine.drain",  # Engine.tick: the loop over nodes' queues
    "jit.call",  # JitRuntime.execute: one operator's call
    "jit.prepare",  # padding, dict state pushed to the device, table sizing
    "jit.grow",  # a keyed table's capacity bump
    "jit.launch",  # the jitted call's dispatch
    "jit.compile",  # a compile: a first call's, or one made ahead
    "jit.fetch",  # device -> host reads of the call's results
    "sink.collect",  # sink tuples built on the host
    "engine.flush_outputs",  # the tick's outputs conformed, joined, routed
    "route",  # one routed batch
    "route.partition",  # key-group partition kernel: put, kernel, fetch
    "route.argsort",  # bucket argsort kernel: put, kernel, fetch
    "route.stats",  # per-batch statistics: send pairs, cross-node usage
    "period.sync_store",  # end_period: device state read into the dicts
    "period.fold",  # end_period: window fold, state bytes, snapshot
    "controller.decide",  # the adaptation framework's decision
    "migration.pause",  # one key group's serialize -> install
    "gc",  # one Python garbage collection
)
#: Spans the engine records besides :data:`SPANS`.  ``chipbench/spans.py``
#: keeps its own copy of :data:`SPANS` (a test holds the two equal) and,
#: until it lists these too, counts their time in their parent span.
EXTRA_SPANS = (
    "jit.retry",  # a call launched again after its new keys overflowed
)

_NULL = contextlib.nullcontext()
_annotation = None  # jax.profiler.TraceAnnotation once enabled
_gc_open = None  # the annotation of the collection in progress


def span(name: str):
    """A context recording ``name`` as a profiler span (no-op until the jit
    tier has called :func:`enable`)."""
    ann = _annotation
    return _NULL if ann is None else ann(name)


def traced(name: str):
    """Decorator: the whole call is one ``name`` span."""

    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return call

    return wrap


def enable() -> None:
    """Turn spans into profiler annotations and record every garbage
    collection as a ``gc`` span (process-wide, once).  Imports jax: only the
    jit tier calls it."""
    global _annotation
    if _annotation is not None:
        return
    from jax.profiler import TraceAnnotation

    _annotation = TraceAnnotation
    gc.callbacks.append(_gc_span)


def _gc_span(phase: str, info: dict) -> None:
    global _gc_open
    if phase == "start":
        _gc_open = _annotation("gc")
        _gc_open.__enter__()
    elif _gc_open is not None:
        ann, _gc_open = _gc_open, None
        ann.__exit__(None, None, None)
