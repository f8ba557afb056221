"""l2c: learned static layer subset replaced by linear approximations
(Learning-to-Cache, offline-calibrated mask).

The mask is static (calibrated offline via ``l2c_mask_from_deltas``), so
the policy carries no cache state at all — masked blocks are *replaced* by
their linear approximators every step, nothing is reused across steps.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import linear_approx
from repro.core.policies.base import F32, CachePolicy, register
from repro.distributed.sharding import constrain


@register("l2c")
class LearnedLayerCache(CachePolicy):
    def __init__(self, model, fc, fc_params, *,
                 l2c_mask: Optional[jax.Array] = None, **kw):
        super().__init__(model, fc, fc_params, **kw)
        self.mask = (l2c_mask if l2c_mask is not None
                     else jnp.zeros((self.L,), bool))

    def init_state(self, batch: int) -> Dict:
        return {"stats": self.init_stats(batch)}

    def step(self, params, state, x_in, c):
        fcp = self.fc_params

        def body(carry, xs):
            x, comp, skip = carry
            bp, w_l, b_l, masked = xs

            x_new = jax.lax.cond(
                masked,
                lambda x: linear_approx.apply_linear(w_l, b_l, x),
                lambda x: self.model.block_apply(bp, x, c), x)
            x_new = constrain(x_new, "act_batch", "act_seq", "act_embed")
            comp = comp + jnp.where(masked, 0.0, 1.0)
            skip = skip + jnp.where(masked, 1.0, 0.0)
            return (x_new, comp, skip), None

        (x_out, comp, skip), _ = jax.lax.scan(
            body, (x_in, jnp.zeros((), F32), jnp.zeros((), F32)),
            (params["blocks"], fcp["W_l"], fcp["b_l"], self.mask))
        eps = self._eps(params, x_out, c)
        st = dict(state)
        stats = dict(st["stats"])
        stats["blocks_computed"] = stats["blocks_computed"] + comp
        stats["blocks_skipped"] = stats["blocks_skipped"] + skip
        stats["blocks_run"] = stats["blocks_run"] + comp
        stats["motion_frac_sum"] = stats["motion_frac_sum"] + 1.0
        st["stats"] = stats
        return eps, st


def l2c_mask_from_deltas(deltas: jax.Array, n_skip: int) -> jax.Array:
    """Learning-to-Cache proxy: skip the n layers whose outputs move the
    residual stream least (offline calibration)."""
    order = jnp.argsort(deltas)
    mask = jnp.zeros(deltas.shape, bool)
    return mask.at[order[:n_skip]].set(True)
