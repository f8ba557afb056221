"""Median of due -> latents on the host over every request due in the
window (host clock)."""
from bench.results import latencies_s, percentile_ms


def read(run):
    return percentile_ms(latencies_s(run), 50)
