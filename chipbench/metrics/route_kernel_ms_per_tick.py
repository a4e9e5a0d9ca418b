"""Host time in the routing kernels' round trips (program spans
``route.partition`` and ``route.argsort``: put, kernel, fetch) per tick of
the window, on the host clock; ``kernel_ms_per_tick`` is their device time."""

from chipbench.spans import ms_per_tick


def read(run):
    return ms_per_tick(run, "route.partition", "route.argsort")
