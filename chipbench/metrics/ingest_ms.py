"""Host time in ``Engine.push_source`` (ingestion, routing) per tick."""

from chipbench.readers import span_ms_per_tick


def read(run):
    return span_ms_per_tick(run, "push_source")
