"""Operations and bytes a kernel needs, computed from its shapes.

``bucket_argsort`` (the engine's stable counting sort, ``kernels/radix_sort``)
must read every code once and write the permutation once: 4 + 4 bytes per
element of its padded int32 input.  Its work is a few integer operations per
element, far below the chip's integer peak at these sizes, so HBM bandwidth
bounds it and its roofline time is ``bytes / hbm_bytes_per_s``.
"""

from __future__ import annotations

INT32 = 4


def bucket_argsort_bytes(n: int) -> int:
    """Bytes one stable argsort of ``n`` int32 codes needs at least."""
    return 2 * INT32 * n
