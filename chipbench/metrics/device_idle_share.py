"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals) / window."""


def read(run):
    t = run.trace
    if not t or not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
