"""95th percentile of due -> latents on the host over every request due in
the window; one never finished counts until the run gave up on it."""
from bench.results import latencies_s, percentile_ms


def read(run):
    return percentile_ms(latencies_s(run), 95)
