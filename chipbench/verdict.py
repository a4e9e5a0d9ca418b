"""``correct``: every number the comparison produced, held to its limit."""

from __future__ import annotations

import numpy as np


def limits_of(config: dict) -> dict:
    """The limit of each number compared: exact counts have 0, the float
    gaps the configuration's limits (PERF.md gives their readings)."""
    lim = config["limits"]
    return {
        "lost": 0,
        "misplaced": 0,
        "unfinished": 0,
        "sum_gap": lim["sum_gap"],
        "state_gap": lim["state_gap"],
    }


def judge(config: dict, values: dict) -> tuple[bool, dict]:
    """``(correct, checks)``: each number beside its limit, and whether
    every one keeps to it."""
    checks = {k: {"value": values[k], "limit": v} for k, v in limits_of(config).items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


def verdict(run) -> tuple[bool, int, int, dict]:
    """``(correct, attempted, failed, checks)`` of a finished run.

    Attempted are the window's events; failed those of them whose results
    never all arrived, or arrived lost, misplaced or beyond the gap limit."""
    a, b = run.window_events
    done = run.completion[a:b]
    never = ~np.isfinite(done)
    correct, checks = judge(run.config, dict(run.checks, unfinished=int(never.sum())))
    bad = np.zeros(b - a, dtype=bool)  # events never offered have no row
    seg = run.bad[a:b]
    bad[: len(seg)] = seg
    return correct, b - a, int((bad | never).sum()), checks
