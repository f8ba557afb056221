"""95th percentile of due -> admitted into a slot (host clock), over the
requests admitted."""
from bench.results import percentile_ms


def read(run):
    return percentile_ms([r.admit_t - r.due for r in run.window.requests
                          if r.admit_t is not None], 95)
