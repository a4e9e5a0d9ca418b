"""Host time folding the period's statistics into a snapshot (program span
``period.fold``: window fold, state bytes, snapshot, hot keys) per tick of
the window."""

from chipbench.spans import ms_per_tick


def read(run):
    return ms_per_tick(run, "period.fold")
