"""Host time building sink tuples (program span ``sink.collect``) per tick
of the window."""

from chipbench.spans import ms_per_tick


def read(run):
    return ms_per_tick(run, "sink.collect")
