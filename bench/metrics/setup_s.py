"""Process start to the window's start: imports, weights, compiles or
their reading back from the cache, and the warm-up."""


def read(run):
    return run.setup_s
