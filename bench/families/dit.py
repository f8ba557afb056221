"""Class-conditional DiT (Peebles & Xie 2023) served by the FastCache
engine: the model family behind the contract in ``bench/spec.py``.

Sizes come from a configuration file in DiT's published key names
(``depth``, ``hidden_size``, ``num_heads``, ``patch_size``, ``input_size``,
``in_channels``, ``mlp_ratio``, ``num_classes``, ``learn_sigma``); the
weights from ``bench/weights.py``; the plain reference from
``bench/reference.py``; operations from ``bench/flops.py``.  A request's
``cond`` is its class label, uniform over the classes.

The comparison (called by ``bench/check.py`` on the copies the window took):

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
program's place for the same steps from the same inputs.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from bench import flops, reference
from bench.check import gated_steps
from bench.flops import shape_of  # noqa: F401  (the contract's)
from bench.loadgen import Request
from bench.reference import algo_of, to_f32  # noqa: F401  (the contract's)
from bench.weights import Dims, dims_of, make_params  # noqa: F401
from bench.weights import request_noise


def model_config(cfg: Dict):
    """The program's model configuration for a configuration file."""
    from repro.configs import get_config
    from repro.configs.base import DiTConfig
    hidden = int(cfg["hidden_size"])
    return get_config(cfg["model"]).replace(
        num_layers=int(cfg["depth"]), d_model=hidden,
        num_heads=int(cfg["num_heads"]), num_kv_heads=int(cfg["num_heads"]),
        d_ff=int(round(cfg["mlp_ratio"] * hidden)), dtype=cfg["dtype"],
        dit=DiTConfig(patch_size=int(cfg["patch_size"]),
                      in_channels=int(cfg["in_channels"]),
                      num_classes=int(cfg["num_classes"]),
                      learn_sigma=bool(cfg["learn_sigma"]),
                      image_size=int(cfg["input_size"])))


def build(cfg: Dict, params, max_steps: int):
    """The serving engine of configuration ``cfg`` over ``params``, exactly
    as the serving launcher makes it (``repro.launch.serve_diffusion``): a
    ``CachedDiT`` runner under the configured cache policy inside a
    ``DiffusionServingEngine`` with ``slots`` slots; and the warm-up's two
    requests (guidance 4 and 1, so both CFG blends run)."""
    from repro.configs.base import FastCacheConfig
    from repro.core import CachedDiT
    from repro.models import build_model
    from repro.serving import DiffusionServingEngine

    model = build_model(model_config(cfg))
    runner = CachedDiT(model, FastCacheConfig(**cfg.get("fastcache", {})),
                       policy=cfg["policy"])
    eng = DiffusionServingEngine(runner, params, max_slots=int(cfg["slots"]),
                                 num_steps=max_steps, max_steps=max_steps)
    warm = (Request(rid=-1, cond=0, steps=3, guidance=4.0, noise_seed=1),
            Request(rid=-2, cond=1, steps=2, guidance=1.0, noise_seed=2))
    return eng, warm


def conds(cfg: Dict, d: Dims) -> int:
    """Class labels, uniform over the model's classes."""
    return d.classes


def to_engine(r: Request, clock: int):
    from repro.serving import DiffusionRequest
    return DiffusionRequest(rid=r.rid, label=r.cond, seed=r.noise_seed,
                            arrival_step=clock, num_steps=r.steps,
                            guidance_scale=r.guidance)


def request_flops(s: flops.Shape, algo: reference.Algo, r: Request,
                  counters: Dict, steps: int) -> float:
    """``flops.request`` over the request's model rows, an unconditional
    row at a guidance of 1 not counted (its counters are taken as half of
    the pair's)."""
    share = 0.5 if r.guidance == 1.0 else 1.0
    return flops.request(
        s, algo.fastcache, rows=2 * share, steps=steps,
        computed=share * counters.get("blocks_computed", 0.0),
        skipped=share * counters.get("blocks_skipped", 0.0))


def kernel_costs(s: flops.Shape, slots: int) -> Dict[str, flops.Cost]:
    """The token-merge kernels' costs at 2 model rows a slot (CFG)."""
    return flops.kernel_costs(s, rows=2 * slots)


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
        lab = jnp.asarray([r.cond], jnp.int32)
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

