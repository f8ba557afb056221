"""1 - (union of device-op intervals) / traced window, in %, from the
profiler's trace."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
