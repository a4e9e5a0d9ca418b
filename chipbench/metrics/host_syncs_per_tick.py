"""Host-device crossings of the jit tier (``EngineMetrics.jit_host_syncs``)
per tick of the window."""


def read(run):
    if not run.window_ticks:
        return None
    return run.counters["jit_host_syncs"] / run.window_ticks
