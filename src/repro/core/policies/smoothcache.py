"""smoothcache: precomputed layer-schedule caching (SmoothCache-style).

SmoothCache observes that a DiT layer's output changes smoothly over
adjacent denoising steps, calibrates per-layer per-step representation
errors offline, and precomputes a *schedule* of (layer, step) pairs whose
block output can be replaced by reusing the layer's cached **residual**
(output minus input) from its last computed step.  At serve time the gate
is a pure table lookup — no statistics, no thresholds.

This policy is the plugin API's front-door proof: it was added as one new
module (registered here, imported from ``core/policies/__init__.py``) and
runs through the sampler, both serving engines and the sharded state
walker without a single edit to ``serving/`` or ``distributed/sharding.py``.

State: the per-layer cached residuals (L, B, N, D), a per-sample step
counter (the schedule position — per-request, so serving slots admitted
mid-flight index the schedule from THEIR step 0) and the warm-up flag.

Construct via the front door::

    CachedDiT(model, fc, policy="smoothcache",
              smooth_schedule=smooth_schedule_from_errors(errors, 0.03))

``smooth_schedule`` is an (L, T) bool table — True at (l, s) reuses layer
l's cached residual on that sample's step s.  Steps beyond T clamp to the
last column.  The default reuses every layer on every other step (a 50%
block-cache ratio), which is SmoothCache's uniform-interval baseline.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.policies.base import F32, CachePolicy, register
from repro.distributed.sharding import constrain

DEFAULT_TABLE_STEPS = 1000


def default_smooth_schedule(num_layers: int, *, interval: int = 2,
                            table_steps: int = DEFAULT_TABLE_STEPS
                            ) -> jax.Array:
    """Uniform-interval schedule: every layer recomputes on step s when
    ``s % interval == 0`` and reuses its cached residual otherwise."""
    s = jnp.arange(table_steps)
    return jnp.broadcast_to(s % interval != 0, (num_layers, table_steps))


def smooth_schedule_from_errors(errors, threshold: float) -> jax.Array:
    """SmoothCache's calibration: ``errors`` (L, T) holds the relative
    change of layer l's output between steps s-1 and s measured on a
    calibration run; (l, s) is cacheable when the observed change stays
    under ``threshold``.  Column 0 always computes (nothing cached yet)."""
    sched = jnp.asarray(errors) < threshold
    return sched.at[:, 0].set(False)


@register("smoothcache")
class SmoothCache(CachePolicy):
    def __init__(self, model, fc, fc_params, *,
                 smooth_schedule: Optional[jax.Array] = None, **kw):
        super().__init__(model, fc, fc_params, **kw)
        self.schedule = (jnp.asarray(smooth_schedule, bool)
                         if smooth_schedule is not None
                         else default_smooth_schedule(self.L))
        if self.schedule.shape[0] != self.L:
            raise ValueError(
                f"smooth_schedule has {self.schedule.shape[0]} layer rows; "
                f"model has {self.L} layers")

    def init_state(self, batch: int) -> Dict:
        m = self.model
        return {
            "prev_delta": jnp.zeros((self.L, batch, self.n_tokens,
                                     m.cfg.d_model), self._state_dtype()),
            "step_count": jnp.zeros((batch,), jnp.int32),
            "have_cache": jnp.zeros((batch,), bool),
            "stats": self.init_stats(batch),
        }

    def reset_rows(self, state, rows):
        st = dict(state)
        st["prev_delta"] = state["prev_delta"].at[:, rows].set(0.0)
        st["step_count"] = state["step_count"].at[rows].set(0)
        st["have_cache"] = state["have_cache"].at[rows].set(False)
        return st

    def step(self, params, state, x_in, c):
        b = x_in.shape[0]
        have = state["have_cache"]                           # (B,)
        pos = jnp.clip(state["step_count"], 0,
                       self.schedule.shape[1] - 1)
        mask = self.schedule[:, pos]                         # (L, B)

        def body(carry, xs):
            x, comp, skip, ran = carry
            bp, delta_prev, m_l = xs
            skip_l = m_l & have                              # (B,)
            reuse = x + delta_prev
            # skip the block entirely when every sample reuses; a mixed
            # batch computes it once and keeps reusing samples' residual
            # sum (bitwise-equal to the all-skip branch for those samples)
            x_new = jax.lax.cond(
                jnp.all(skip_l),
                lambda ops_: ops_[0],
                lambda ops_: jnp.where(skip_l[:, None, None], ops_[0],
                                       self.model.block_apply(bp, ops_[1],
                                                              c)),
                (reuse, x))
            x_new = constrain(x_new, "act_batch", "act_seq", "act_embed")
            delta_new = jnp.where(skip_l[:, None, None], delta_prev,
                                  x_new - x)
            sk = skip_l.astype(F32)
            ran = ran + jnp.where(jnp.all(skip_l), 0.0, 1.0)
            return (x_new, comp + (1.0 - sk), skip + sk, ran), delta_new

        (x_out, comp, skip, ran), new_delta = jax.lax.scan(
            body, (x_in, jnp.zeros((b,), F32), jnp.zeros((b,), F32),
                   jnp.zeros((), F32)),
            (params["blocks"], state["prev_delta"], mask))
        eps = self._eps(params, x_out, c)

        st = dict(state)
        st["prev_delta"] = new_delta
        st["step_count"] = state["step_count"] + 1
        st["have_cache"] = jnp.ones_like(have)
        stats = dict(st["stats"])
        stats["blocks_computed"] = stats["blocks_computed"] + comp
        stats["blocks_skipped"] = stats["blocks_skipped"] + skip
        stats["blocks_run"] = stats["blocks_run"] + ran
        stats["motion_frac_sum"] = stats["motion_frac_sum"] + 1.0
        st["stats"] = stats
        return eps, st
