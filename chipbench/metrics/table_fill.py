"""Share of the keyed device tables' slots that hold an entry at the
window's close (``EngineMetrics.table_entries`` over ``table_capacity``,
as the controller records them at the last period's end)."""


def read(run):
    if not run.periods:
        return None
    last = run.periods[-1]
    capacity = getattr(last, "table_capacity", 0)
    if not capacity:
        return None
    return 100.0 * last.table_entries / capacity
