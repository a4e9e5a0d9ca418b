"""Share of the device's idle time in the window in which the host was in
no program span (only in a benchmark span, or in none)."""

from chipbench.spans import SPANS


def read(run):
    program = (run.trace or {}).get("program")
    if program is None:
        return None
    idle = program["idle_by_span"]
    total = sum(idle.values())
    if not total:
        return None
    return 100.0 * sum(v for k, v in idle.items() if k not in SPANS) / total
