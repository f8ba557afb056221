"""The comparison that decides ``correct``, apart from the model.

Before the window, a sample of its requests is drawn from the run's seed,
the longest of them first (``plan``); the window copies each sampled
request's output and slot state (the engine's own slot snapshot) after its
first step, after its second, and around one later step drawn from the
seed.  Once the window has closed, the model family's plain reference
recomputes those steps and names the gaps (``reference_outputs``, ``gaps``
and ``rule_breaks`` of ``bench/families/<family>.py``); ``verdict`` holds
each number to its limit.  ``bench/calibrate.py`` reads the control and the
program's own readings, from which the limits in each configuration's file
were set.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bench.loadgen import Request

TAG = 0x5EED


def pick(candidates: Sequence[Request], k: int, seed: int) -> List[Request]:
    """``k`` of ``candidates`` drawn from ``seed``, the longest first."""
    if not candidates:
        return []
    longest = max(candidates, key=lambda r: (r.steps, -r.rid))
    rest = [r for r in candidates if r is not longest]
    rng = np.random.default_rng([seed, TAG])
    take = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(take)]


def tap_steps(r: Request, seed: int) -> Tuple[int, ...]:
    """The steps after which the window copies request ``r``'s slot: its
    first, its second, and a step g drawn in [3, n - 1] with the one before
    it (a step that ends a request frees its slot, so n is not drawn)."""
    n = r.steps
    steps = {1}
    if n > 2:
        steps.add(2)
    if n > 3:
        g = int(np.random.default_rng([seed, r.rid, TAG]).integers(3, n))
        steps |= {g - 1, g}
    return tuple(sorted(steps))


def plan(candidates: Sequence[Request], k: int, seed: int
         ) -> Dict[int, Tuple[int, ...]]:
    """rid -> steps to copy, for the sample of ``k`` drawn from ``seed``."""
    return {r.rid: tap_steps(r, seed) for r in pick(candidates, k, seed)}


def sampled(requests: Sequence[Request], planned: Dict[int, Tuple[int, ...]]
            ) -> List[Request]:
    """The planned requests that finished, with every copy taken."""
    return [r for r in requests if r.rid in planned and r.latents is not None
            and all(j in r.taps for j in planned[r.rid])]


def gated_steps(r: Request) -> List[int]:
    return [j for j in sorted(r.taps) if j >= 2 and j - 1 in r.taps]


def served_outputs(sample: Sequence[Request]
                   ) -> Dict[int, Dict[int, np.ndarray]]:
    return {r.rid: {j: np.asarray(r.taps[j]["x"]) for j in
                    [1] + gated_steps(r)} for r in sample}


def verdict(values: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, Optional[float]]]]:
    """Every number at or under its limit.  A number that is not finite,
    or has no limit, fails."""
    shown, ok = {}, True
    for name, value in values.items():
        limit = limits.get(name)
        good = (limit is not None and np.isfinite(value)
                and value <= limit)
        ok &= bool(good)
        shown[name] = {"value": value,
                       "limit": None if limit is None else float(limit)}
    return ok, shown
