"""Share of its roofline the stable bucket argsort (``kernels/radix_sort``)
reaches: the bytes its calls need (``costs.bucket_argsort_bytes`` of each
call's padded input) over its device time at the chip's HBM bandwidth.
Bandwidth bounds it (a few integer operations per element).

A call is two Pallas kernels: the histogram pass takes the codes alone, the
rank pass the codes and the per-block bases; the time is both passes'."""

import math

from chipbench import costs, peaks


def read(run):
    t = run.trace
    if not t or not t.get("kernels"):
        return None
    nbytes, seconds = 0, 0.0
    for kernel, shapes, dur in t["kernels"]:
        if kernel != "bucket_argsort":
            continue
        seconds += dur
        if len(shapes) == 1:  # the histogram pass: one per call
            nbytes += costs.bucket_argsort_bytes(math.prod(shapes[0]))
    if not seconds or not nbytes:
        return None
    bw = peaks.peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * nbytes / (seconds * bw)
