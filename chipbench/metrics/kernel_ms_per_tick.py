"""Device time of the engine's Pallas kernels (key-group partition, bucket
argsort, and the radix passes inside the keyed running sums) per tick."""


def read(run):
    t = run.trace
    if not t or not t.get("kernels") or not run.window_ticks:
        return None
    return 1e3 * sum(d for _, _, d in t["kernels"]) / run.window_ticks
