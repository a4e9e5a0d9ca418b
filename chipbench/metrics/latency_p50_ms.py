"""Median event-to-result latency over all window events (open loop)."""

from chipbench.readers import latency_ms


def read(run):
    return latency_ms(run, 50)
