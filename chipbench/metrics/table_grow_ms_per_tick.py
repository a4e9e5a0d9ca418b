"""Host milliseconds per tick of the window spent because a keyed table
changed capacity (``PeriodMetrics.table_grow_s`` summed over the window's
periods: the grows, the first launch of each shape at a new capacity, and
relaunches after overflows); ``None`` where the program records no such
counter."""

from chipbench.spans import window_sum


def read(run):
    seconds = window_sum(run, "table_grow_s")
    if not run.window_ticks or seconds is None:
        return None
    return 1e3 * seconds / run.window_ticks
