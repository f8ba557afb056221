"""What a run hands to the metric readers (``bench/metrics/*.py``), and the
arithmetic that several of them share."""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional

import numpy as np

from bench.window import WindowResult


@dataclasses.dataclass
class RunData:
    cell: Any                         # spec.Cell
    dims: Any                         # the family's sizes
    shape: Any                        # the family's shape_of(dims, algo)
    algo: Any                         # the family's algo_of(config)
    window: WindowResult
    setup_s: float
    peak: Any = None                  # peaks.Peak; None off the chip
    memory_peak_bytes: Optional[int] = None
    trace: Any = None                 # trace_reduce.Summary with --trace 1
    family: Any = None                # the module bench/families/<family>.py


def latencies_s(run: RunData) -> List[float]:
    """Due to latents-on-host, per attempted request; one that never
    finished counts as waiting until the run gave up on it."""
    w = run.window
    return [(r.done_t if r.done_t is not None else w.drained_s) - r.due
            for r in w.requests]


def percentile_ms(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    return 1e3 * float(np.percentile(np.asarray(values, np.float64), q))


def required_flops(run: RunData) -> float:
    """FLOPs that the requests' steps inside the window needed, by the
    family's count (``request_flops``) from each request's counters,
    harvested at completion or read from its slot at the close."""
    w = run.window
    total = 0.0
    for r in w.requests:
        if r.rid in w.in_flight:
            counters, steps = w.in_flight[r.rid], w.steps_done[r.rid]
        elif r.done_t is not None and r.done_t <= w.close_s:
            counters, steps = r.cache, r.steps
        else:
            continue
        total += run.family.request_flops(run.shape, run.algo, r, counters,
                                          steps)
    return total


def roofline(run: RunData, kernels) -> Optional[float]:
    """Least time the kernels' traced calls need at the cell's shapes over
    their device time, in %; None where the trace holds none of them."""
    t = run.trace
    if t is None or run.peak is None:
        return None
    costs = run.family.kernel_costs(run.shape, int(run.cell.config["slots"]))
    need = spent = 0.0
    for k in kernels:
        calls, secs = t.kernels.get(k, (0, 0.0))
        if calls:
            need += calls * costs[k].seconds(run.peak)
            spent += secs
    return 100.0 * need / spent if spent > 0 else None
