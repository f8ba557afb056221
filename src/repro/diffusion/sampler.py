"""DDIM sampler with classifier-free guidance and cache-policy hooks.

The sampler drives a ``CachedDiT`` runner: every denoising step is one
runner.step call, so any cache policy (nocache / fastcache / baselines) slots
in unchanged.  CFG doubles the batch (cond + null label) — the cache state is
sized 2B and cond/uncond streams are cached independently, matching how the
paper runs DiT with guidance enabled (§5.2).

``denoise_step`` is the reusable single-step core: one model evaluation +
guidance + DDIM update over per-sample ``(t, t_prev)`` vectors.  ``sample()``
loops it over a shared schedule; the continuous-batching engine
(``serving/diffusion_engine.py``) jits it with a heterogeneous per-slot
timestep vector so requests at different schedule positions share one batch.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.runner import CachedDiT
from repro.diffusion import schedule as sch

F32 = jnp.float32

GuidanceLike = Union[float, int, jax.Array]


def denoise_step(runner: CachedDiT, params, sched: sch.Schedule, state,
                 x: jax.Array, t: jax.Array, t_prev: jax.Array,
                 labels: jax.Array, *, guidance_scale: GuidanceLike = 4.0,
                 model_eval=None, return_eps: bool = False):
    """One denoising step x_t -> x_{t_prev} for a (possibly heterogeneous)
    batch: per-sample integer timesteps ``t``/``t_prev`` (B,), per-sample
    ``labels`` (B,).  With guidance the model batch is doubled internally
    (cond rows then uncond rows) and ``state`` must be sized 2B; the split
    matches ``CachedDiT.init_state(2 * B)``.  ``t_prev < 0`` marks the final
    step (x0 prediction).  Returns (x_next, new_state).

    ``guidance_scale`` may be a Python scalar (shared across the batch; the
    value 1.0 statically disables CFG, and ``state`` is sized B) or a (B,)
    array of per-sample scales.  The array form ALWAYS materializes the CFG
    rows — heterogeneity is expressed in the blend weights, with
    ``scale == 1.0`` rows selecting the conditional eps outright so they
    stay bitwise-equal to an unguided run of that sample.

    ``model_eval`` replaces ``runner.step`` (same signature) — the audit
    plane (obs/audit.py) uses it to route the identical CFG/guidance/DDIM
    plumbing through the uncached full forward.  ``return_eps`` additionally
    returns the post-guidance-blend eps (B, ...) as a third element, the
    quantity the audit plane compares cached-vs-true."""
    per_sample = not isinstance(guidance_scale, (int, float))
    use_cfg = per_sample or guidance_scale != 1.0
    b = x.shape[0]
    # named_scope phases become the ops' name paths in the compiled step,
    # so an XLA-level profile (beside the engine's host spans,
    # obs.tracing) attributes time to CFG doubling / model eval / guidance
    # blend / DDIM update by name
    if use_cfg:
        with jax.named_scope("cfg_double"):
            null_label = runner.model.cfg.dit.num_classes
            x_in = jnp.concatenate([x, x], axis=0)
            t_in = jnp.concatenate([t, t], axis=0)
            lab = jnp.concatenate([labels,
                                   jnp.full((b,), null_label, jnp.int32)])
    else:
        x_in, t_in, lab = x, t, labels
    eval_fn = runner.step if model_eval is None else model_eval
    with jax.named_scope("model_eval"):
        eps, state = eval_fn(params, state, x_in, t_in, lab)
    if use_cfg:
        with jax.named_scope("cfg_blend"):
            eps_c, eps_u = jnp.split(eps, 2, axis=0)
            if per_sample:
                g = jnp.broadcast_to(
                    jnp.asarray(guidance_scale, F32), (b,)
                ).reshape((b,) + (1,) * (x.ndim - 1))
                # scale==1.0 must reduce to eps_c EXACTLY: the algebraic
                # form eps_u + 1.0*(eps_c - eps_u) re-associates in float32
                # and would break bitwise parity with an unguided solo run
                eps = jnp.where(g == 1.0, eps_c,
                                eps_u + g * (eps_c - eps_u))
            else:
                eps = eps_u + guidance_scale * (eps_c - eps_u)
    with jax.named_scope("ddim_update"):
        x = sch.ddim_step(sched, x, eps, t, t_prev)
    if return_eps:
        return x, state, eps
    return x, state


# sample()'s jitted step, shared across calls: one compiled program per
# (runner, scalar guidance) or per runner for per-sample guidance vectors.
# A fresh jax.jit per call would retrace and recompile the whole DiT step
# for every solo run (tens of seconds at DiT-XL/2 on a TPU).
_jit_step = jax.jit(denoise_step, static_argnums=(0,),
                    static_argnames=("guidance_scale",))
_jit_step_per_sample = jax.jit(denoise_step, static_argnums=(0,))


def sample(runner: CachedDiT, params, key: jax.Array, *, batch: int,
           labels: Optional[jax.Array] = None, num_steps: int = 50,
           guidance_scale: GuidanceLike = 4.0, num_train_steps: int = 1000,
           jit_step: bool = True, t_offsets: Optional[jax.Array] = None,
           x_init: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Returns (samples (B, H, W, C) latents, cache stats state).

    The batch may be heterogeneous: per-sample ``labels`` (B,) and per-sample
    integer ``t_offsets`` (B,) that shift each sample's DDIM schedule — the
    per-sample cache gate keeps each sample's skip decisions independent, so
    mixing fast-converging and still-moving samples in one batch is safe.
    ``x_init`` overrides the initial noise (e.g. to match unbatched runs)."""
    cfg = runner.model.cfg
    img, ch = cfg.dit.image_size, cfg.dit.in_channels
    if labels is None:
        labels = jnp.zeros((batch,), jnp.int32)
    # per-sample guidance vectors always run the doubled CFG batch (see
    # denoise_step); scalar 1.0 statically disables CFG
    use_cfg = (not isinstance(guidance_scale, (int, float))
               or guidance_scale != 1.0)

    sched = sch.linear_schedule(num_train_steps)
    ts = sch.ddim_timesteps(num_train_steps, num_steps)
    ts_prev = jnp.concatenate([ts[1:], jnp.array([-1], jnp.int32)])

    x = (x_init.astype(F32) if x_init is not None
         else jax.random.normal(key, (batch, img, img, ch), F32))
    eff_batch = 2 * batch if use_cfg else batch
    state = runner.init_state(eff_batch)

    off = (jnp.zeros((batch,), jnp.int32) if t_offsets is None
           else t_offsets.astype(jnp.int32))

    step = denoise_step
    if jit_step:
        step = (_jit_step if isinstance(guidance_scale, (int, float))
                else _jit_step_per_sample)
    step_fn = functools.partial(step, runner, guidance_scale=guidance_scale)

    for i in range(num_steps):
        t = jnp.clip(ts[i] + off, 0, num_train_steps - 1)
        t_prev = jnp.where(ts_prev[i] < 0, -1,
                           jnp.clip(ts_prev[i] + off, 0,
                                    num_train_steps - 1))
        x, state = step_fn(params, sched, state, x, t, t_prev, labels)
    return x, state
