"""95th percentile event-to-result latency over all window events (open
loop): the tail a reconfiguration pause sets."""

from chipbench.readers import latency_ms


def read(run):
    return latency_ms(run, 95)
