"""The FLOPs that the window's served work needed (the model family's
``request_flops``, from each request's counters at its completion or at
the close; for DiT ``bench/flops.py``: active rows only, a cached block as
its linear approximation, a first step as every block on every token),
over the engine-busy time on the host's clock and the chip's bf16 peak,
in %.
The busy time holds the host's gaps between steps, which
``device_idle_share`` reads from the trace, so a host stall lowers both."""
from bench.results import required_flops


def read(run):
    w = run.window
    if run.peak is None or w.busy_s <= 0:
        return None
    return 100.0 * required_flops(run) / (w.busy_s * run.peak.bf16_flops)
