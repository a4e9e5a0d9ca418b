"""Process start to the first timed tick: imports, compilation, warm-up."""


def read(run):
    return run.setup_s
