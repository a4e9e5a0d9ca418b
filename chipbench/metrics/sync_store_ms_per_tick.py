"""Host time reading the device state into the store at each period's end
(program span ``period.sync_store``) per tick of the window."""

from chipbench.spans import ms_per_tick


def read(run):
    return ms_per_tick(run, "period.sync_store")
