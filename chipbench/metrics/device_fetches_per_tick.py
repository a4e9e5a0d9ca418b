"""Blocking device→host reads per tick of the window
(``EngineMetrics.device_fetches``, summed over the window's periods as the
controller records them): jit results, table reads, routing-kernel results."""

from chipbench.spans import window_sum


def read(run):
    fetches = window_sum(run, "device_fetches")
    if not run.window_ticks or fetches is None:
        return None
    return fetches / run.window_ticks
