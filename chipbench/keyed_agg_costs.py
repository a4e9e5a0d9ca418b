"""Bytes a keyed multi-aggregate table update must move, computed from its
tuple count (the byte function behind ``metrics/keyed_agg_roofline.py``).

Each tuple of q17's call (``repro.data.nexmark``) has to be read once — its
table code's inputs, auction and date_time, and the price every column
takes its term from: three int64 — and its seven running values (count,
three counts-if, min, max, sum) written once: seven int64.  That is 80
bytes a tuple.  The table's own side (the lookup, the merge of new codes,
the writes of the rows) is left out, so the count is a lower bound, and a
share of the roofline built on it reads below its true value, never above.
The work is a few integer operations a byte, far below the chip's integer
peak, so HBM bandwidth bounds it: roofline time is ``bytes /
hbm_bytes_per_s``.
"""

from __future__ import annotations

INT64 = 8
#: int64 columns each tuple is read from, and running values it writes.
INPUTS = 3
COLUMNS = 7


def keyed_agg_bytes(tuples: int, inputs: int = INPUTS, columns: int = COLUMNS) -> int:
    """Bytes ``tuples`` tuples' updates of a ``columns``-column table need
    at least."""
    return INT64 * (inputs + columns) * tuples
