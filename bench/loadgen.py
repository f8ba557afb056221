"""The one traffic generator.  A mix file (``bench/mixes/<name>.json``)
holds only parameters:

    arrival    "poisson": open loop, requests due at times in seconds;
               "backlog": closed, the queue always holds ``depth`` waiting
               requests (``depth`` defaults to the engine's slots)
    rate       requests/s (poisson), or
    segments   [[until_s, rate], ...] a piecewise rate, repeated every
               ``period_s`` when that is given (bursts)
    steps      {"<DDIM steps>": share, ...}
    guidance   {"<guidance scale>": share, ...}

Every request carries an integer conditioning ``cond``, uniform over
range(conds), where the model family sets ``conds`` and maps ``cond`` to
what the program takes (``bench/families/<family>.py``), and a noise seed
of its own, both drawn from the run's seed.

Every seed gets the same work in another order: the arrival times are one
draw fixed by the mix and the window (the exponential distribution's
quantiles at (i + 1/2)/n as the inter-arrival gaps, in an order drawn
once, their sum scaled to the window), and the seed deals the requests
onto them: the step budgets and guidance scales, which are the mix's
shares rounded to whole requests, in its own order, and every
conditioning and noise seed.  So two seeds differ in which request arrives
when, and not in how much work arrives or when.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, Iterator, List, Optional, Tuple, Union

import numpy as np


ARRIVAL_ORDER = 0      # the fixed draw that orders the gaps


@dataclasses.dataclass(eq=False)
class Request:
    rid: int
    cond: int                         # the family's conditioning
    steps: int
    guidance: float
    noise_seed: int
    due: float = 0.0                  # seconds from the window's start
    admit_t: Optional[float] = None
    done_t: Optional[float] = None
    latents: Optional[np.ndarray] = None
    cache: Optional[Dict] = None
    # step -> copy of the request's slot after it (bench/check.py)
    taps: Dict[int, Dict] = dataclasses.field(default_factory=dict)
    slot: int = -1
    replica: int = 0                  # the engine that served it


def _shares(mix: Dict, key: str, n: int, rng: np.random.Generator,
            cast) -> List:
    """``n`` values with the mix's shares, rounded by largest remainder
    (ties to the earlier value), shuffled."""
    items = [(cast(k), float(v)) for k, v in mix[key].items()]
    total = sum(v for _, v in items)
    exact = [n * v / total for _, v in items]
    counts = [int(np.floor(e)) for e in exact]
    order = sorted(range(len(items)), key=lambda i: counts[i] - exact[i])
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    values = [items[i][0] for i, c in enumerate(counts) for _ in range(c)]
    rng.shuffle(values)
    return values


def _rate_integral(mix: Dict, t: np.ndarray) -> np.ndarray:
    """Expected arrivals in [0, t)."""
    if "segments" not in mix:
        return float(mix["rate"]) * t
    seg = [(float(u), float(r)) for u, r in mix["segments"]]
    period = float(mix.get("period_s", 0.0))

    def within(x):
        acc, lo = 0.0, 0.0
        for until, r in seg:
            acc += r * max(0.0, min(x, until) - lo)
            lo = until
        return acc + seg[-1][1] * max(0.0, x - lo)

    if period <= 0:
        return np.vectorize(within)(t)
    per = within(period)
    return np.vectorize(lambda x: (x // period) * per
                        + within(x % period))(t)


def poisson(mix: Dict, seed: int, seconds: float,
            conds: int) -> List[Request]:
    """Requests due in [0, seconds), in order of due time."""
    total = float(_rate_integral(mix, np.asarray(seconds)))
    n = max(1, int(round(total)))
    q = (np.arange(n + 1) + 0.5) / (n + 1)
    gaps = -np.log1p(-q)
    np.random.default_rng(ARRIVAL_ORDER).shuffle(gaps)
    rng = np.random.default_rng(seed)
    # arrival times in operational time (unit rate) over [0, total), then
    # mapped back through the rate's integral
    op = np.cumsum(gaps)[:n] * (total / gaps.sum())
    grid = np.linspace(0.0, seconds, 4097)
    due = np.interp(op, _rate_integral(mix, grid), grid)
    steps = _shares(mix, "steps", n, rng, int)
    guidance = _shares(mix, "guidance", n, rng, float)
    cond = rng.integers(0, conds, n)
    noise = rng.integers(0, 2**31 - 1, n)
    return [Request(rid=i, cond=int(cond[i]), steps=steps[i],
                    guidance=guidance[i], noise_seed=int(noise[i]),
                    due=float(due[i])) for i in range(n)]


def backlog(mix: Dict, seed: int, conds: int,
            block: int = 20) -> Iterator[Request]:
    """An endless stream; each run of ``block`` requests holds the mix's
    shares exactly."""
    rng = np.random.default_rng(seed)
    rid = itertools.count()
    while True:
        steps = _shares(mix, "steps", block, rng, int)
        guidance = _shares(mix, "guidance", block, rng, float)
        for s, g in zip(steps, guidance):
            yield Request(rid=next(rid), cond=int(rng.integers(conds)),
                          steps=s, guidance=g,
                          noise_seed=int(rng.integers(0, 2**31 - 1)))


def traffic(mix: Dict, seed: int, seconds: float, conds: int,
            slots: int) -> Tuple[Union[List[Request], Iterator[Request]], int]:
    """A window's traffic for ``bench/window.drive``: the open-loop list,
    or the backlog's stream with the number of requests it keeps waiting."""
    if mix["arrival"] == "poisson":
        return poisson(mix, seed, seconds, conds), 0
    if mix["arrival"] == "backlog":
        return backlog(mix, seed, conds), int(mix.get("depth", slots))
    raise ValueError(f"unknown arrival {mix['arrival']!r}")


def candidates(mix: Dict, seed: int, seconds: float, conds: int,
               slots: int) -> List[Request]:
    """The requests a check may sample before the window runs: every one
    due in it (open loop), or the backlog's first two slots' worth, which
    the window admits in its first two rounds."""
    if mix["arrival"] == "backlog":
        return list(itertools.islice(backlog(mix, seed, conds), 2 * slots))
    return poisson(mix, seed, seconds, conds)


def max_steps(mix: Dict) -> int:
    return max(int(k) for k in mix["steps"])
