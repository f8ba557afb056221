"""The comparison that decides ``correct``.

Before the window, a sample of its requests is drawn from the run's seed,
the longest of them first (``plan``); the window copies each sampled
request's latents and slot state (the engine's own slot snapshot) after its
first step, after its second, and around one later step drawn from the
seed.  Once the window has closed, the plain reference
(``bench/reference.py``) recomputes those steps:

  first_step_gap     the served latents after the request's first step
                     (every block on every token, inside a batch that
                     other requests share) against the reference's step
                     from the request's noise, as a share of the
                     reference step's update |x1_ref - noise|;
  gated_step_gap     the served latents after step 2 and after the drawn
                     step g (3 <= g < n) against the reference's
                     teacher-forced step: from the program's latents and
                     cache state before the step, with the motion tokens
                     and cached blocks that the program chose and the
                     merge that Eqs. 10-13 give on its own tokens, in
                     float32 (the linear bypass and its blend, the cached
                     blocks' approximations, the computed blocks, merging,
                     CFG and DDIM), as a share of the reference step's
                     update;
  cache_rule_breaks  the program's decisions at those steps against
                     Alg. 1 on the program's own values (motion partition,
                     chi-square gate, variance trackers; a second step's
                     trackers see their first observation), see
                     ``reference.rule_breaks``; exact, limit 0.

Each gap is the widest over the sample.  The control is the reference
computed with every matmul operand in float8 (``quant=True``), put in the
program's place for the same steps from the same inputs;
``bench/calibrate.py`` reads it and the program's own readings, from which
the limits in each configuration's file were set.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from bench import reference
from bench.loadgen import Request
from bench.weights import Dims, request_noise

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


def _state(tap, d: Dims, a: reference.Algo) -> Dict:
    """A program slot snapshot's state in the reference's layout."""
    st = tap["state"]
    f32 = lambda v: jnp.asarray(np.asarray(v, np.float32))  # noqa: E731
    out = reference.init_state(2, d, a)
    if a.fastcache:
        out.update(tokens=f32(st["prev_tokens_in"]),
                   hidden=f32(st["prev_hidden"]),
                   sigma2=f32(st["gate"].sigma2))
    if a.merge_window:
        out["merge_prev"] = f32(st["tokred"]["prev_full"])
    return out


def _force(before, after, a: reference.Algo) -> Dict:
    """The decisions of a step, taken as given by the teacher-forced
    reference.  Token merging's centres and assignments: Eqs. 10-13 on the
    program's own full tokens before and after the step.  FastCache's
    motion tokens and cached blocks: those the program chose, its kept
    tokens (first block input changed) topped up to C by the saliency of
    its own tokens, and the blocks whose tracker it kept."""
    s0, s1 = before["state"], after["state"]
    force = {}
    if a.merge_window:
        full = [jnp.asarray(np.asarray(s["tokred"]["prev_full"], np.float32))
                for s in (s1, s0)]
        force["centres"] = reference.merge_decisions(*full, a=a)
    if not a.fastcache:
        return force
    tok0 = np.asarray(s0["prev_tokens_in"], np.float64)
    tok1 = np.asarray(s1["prev_tokens_in"], np.float64)
    h0, h1 = s0["prev_hidden"][0], s1["prev_hidden"][0]
    nb, n = tok0.shape[:2]
    cap = max(1, int(round(a.capacity * n)))
    idx = np.zeros((nb, cap), np.int32)
    keep = np.zeros((nb, cap), bool)
    for b in range(nb):
        sal = np.sum(np.square(tok1[b] - tok0[b]), axis=-1)
        kept = np.any(np.asarray(h1[b]) != np.asarray(h0[b]), axis=-1)
        order = sorted(range(n), key=lambda t: (not kept[t], -sal[t]))
        idx[b] = order[:cap]
        keep[b] = kept[idx[b]]
    cached = (np.asarray(s1["gate"].sigma2)
              == np.asarray(s0["gate"].sigma2))
    force["motion"] = (jnp.asarray(idx), jnp.asarray(keep),
                       jnp.asarray(cached))
    return force


def reference_outputs(p32, d: Dims, algo: reference.Algo,
                      sample: Sequence[Request], quant: bool = False
                      ) -> Dict[int, Dict[int, np.ndarray]]:
    """rid -> {step: the reference's latents after it}: step 1 from the
    request's noise, each gated step teacher-forced from the program."""
    ac = jnp.asarray(reference.alphas_cumprod(), jnp.float32)
    out = {}
    for r in sample:
        ts, prev = reference.ddim_timesteps(r.steps)
        lab = jnp.asarray([r.label], jnp.int32)
        gui = jnp.asarray([r.guidance], jnp.float32)

        def step(st, x, j, first, force=None):
            y, _ = reference.guided_step(
                p32, st, jnp.asarray(x, jnp.float32)[None],
                jnp.asarray([ts[j - 1]], jnp.int32),
                jnp.asarray([prev[j - 1]], jnp.int32), lab, gui, ac, force,
                d=d, a=algo, quant=quant, first=first)
            return np.asarray(y[0])

        got = {1: step(reference.init_state(2, d, algo),
                       request_noise(r.noise_seed, d), 1, True)}
        for j in gated_steps(r):
            before, after = r.taps[j - 1], r.taps[j]
            got[j] = step(_state(before, d, algo), before["x"], j, False,
                          _force(before, after, algo))
        out[r.rid] = got
    return out


def served_outputs(sample: Sequence[Request]
                   ) -> Dict[int, Dict[int, np.ndarray]]:
    return {r.rid: {j: np.asarray(r.taps[j]["x"]) for j in
                    [1] + gated_steps(r)} for r in sample}


def gaps(d: Dims, sample: Sequence[Request],
         served: Dict[int, Dict[int, np.ndarray]],
         ref: Dict[int, Dict[int, np.ndarray]]) -> Dict[str, float]:
    """Both gaps; ``served`` maps rid -> {step: latents after it}.  A step
    starts from the request's noise (step 1) or from the program's latents
    before it."""
    first, gated = 0.0, 0.0
    for r in sample:
        for j, want in ref[r.rid].items():
            start = (request_noise(r.noise_seed, d) if j == 1
                     else r.taps[j - 1]["x"])
            want = np.asarray(want, np.float64)
            step = np.linalg.norm(want - np.asarray(start, np.float64))
            gap = float(np.linalg.norm(np.asarray(served[r.rid][j],
                                                  np.float64) - want) / step)
            if j == 1:
                first = max(first, gap)
            else:
                gated = max(gated, gap)
    return {"first_step_gap": first, "gated_step_gap": gated}


def rule_breaks(sample: Sequence[Request], algo: reference.Algo
                ) -> Tuple[int, int]:
    """(cache_rule_breaks, rows unread) over the sample's gated steps."""
    breaks = unread = 0
    if not algo.fastcache:
        return 0, 0
    for r in sample:
        for j in gated_steps(r):
            s0, s1 = r.taps[j - 1]["state"], r.taps[j]["state"]
            b, u = reference.rule_breaks(
                s0["prev_tokens_in"], s1["prev_tokens_in"],
                s0["prev_hidden"], s1["prev_hidden"],
                s0["gate"].sigma2, s1["gate"].sigma2, j >= 3, algo)
            breaks, unread = breaks + b, unread + u
    return breaks, unread


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
