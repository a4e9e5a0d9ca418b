"""Host time in ``Engine.tick`` (drain, jit calls, output routing) per
tick of the window."""

from chipbench.readers import span_ms_per_tick


def read(run):
    return span_ms_per_tick(run, "tick")
