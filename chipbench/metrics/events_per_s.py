"""Events whose results reached every sink in the window, per second of
the window's wall time (stalls included)."""


def read(run):
    done = run.completion
    n = int(((done > run.t0) & (done <= run.t1)).sum())
    return n / (run.t1 - run.t0)
