"""Host time routing batches (program span ``route``: every routed batch,
from ``push_source`` and from the tick's outputs) per tick of the window."""

from chipbench.spans import ms_per_tick


def read(run):
    return ms_per_tick(run, "route")
