"""Host time in the jit tier's device→host reads of its results (program
span ``jit.fetch``: the wait for the call to finish, then the copies) per
tick of the window."""

from chipbench.spans import ms_per_tick


def read(run):
    return ms_per_tick(run, "jit.fetch")
