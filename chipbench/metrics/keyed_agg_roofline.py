"""Share of its roofline the q17 keyed multi-aggregate update reaches: the
bytes its tuples must move (``keyed_agg_costs.keyed_agg_bytes`` of the
window's ``sink_tuples``, a lower bound) over the device time of q17's
jitted program at the chip's HBM bandwidth.

The device time is every operation of ``device_ops`` whose module is the
program (``jit__q17_jit``): the trace's reduction keeps the ten longest
operations of the window, so an operation of the program outside them is
not counted.  ``None`` where the trace holds no such program."""

from chipbench import keyed_agg_costs, peaks

MODULE = "jit__q17_jit"


def read(run):
    t = run.trace
    if not t or not t.get("device_ops"):
        return None
    seconds = sum(s for name, s in t["device_ops"] if name.split(":")[0] == MODULE)
    tuples = run.counters.get("sink_tuples", 0)
    if not seconds or not tuples:
        return None
    bw = peaks.peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * keyed_agg_costs.keyed_agg_bytes(tuples) / (seconds * bw)
