"""Host time of ``execute_plan`` (redirect, serialize, install), mean over
the window's periods that adapted."""

from chipbench.readers import span_ms_mean


def read(run):
    return span_ms_mean(run, "execute_plan")
