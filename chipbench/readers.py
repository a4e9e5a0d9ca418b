"""Arithmetic shared by the metric readers in ``metrics/``."""

from __future__ import annotations

import numpy as np


def span_ms_per_tick(run, name: str):
    """Host milliseconds inside ``name`` spans per tick of the window."""
    if not run.window_ticks:
        return None
    return 1e3 * sum(b - a for a, b in run.window_spans(name)) / run.window_ticks


def span_ms_mean(run, name: str):
    """Mean host milliseconds of the window's ``name`` spans."""
    spans = run.window_spans(name)
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)


def latency_ms(run, q: float):
    """The ``q``-th percentile over all window events of the time from an
    event's scheduled creation to its last sink result on the host."""
    a, b = run.window_events
    if run.traffic.get("loop") != "open" or b <= a:
        return None
    lat = run.completion[a:b] - run.due_time(np.arange(a, b))
    val = float(np.percentile(lat, q))
    return 1e3 * val if np.isfinite(val) else None
