"""Host time in Python garbage collections (program span ``gc``) per tick
of the window."""

from chipbench.spans import ms_per_tick


def read(run):
    return ms_per_tick(run, "gc")
