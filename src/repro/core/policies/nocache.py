"""nocache: full compute every step (the exact reference sampler).

Carries no cache state at all — just the standard stats block.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core.policies.base import CachePolicy, register


@register("nocache")
class NoCache(CachePolicy):
    def init_state(self, batch: int) -> Dict:
        return {"stats": self.init_stats(batch)}

    def step(self, params, state, x_in, c):
        x_out, _ = self._full_forward(params, x_in, c)
        eps = self._eps(params, x_out, c)
        st = dict(state)
        stats = dict(st["stats"])
        stats["blocks_computed"] = stats["blocks_computed"] + float(self.L)
        stats["blocks_run"] = stats["blocks_run"] + float(self.L)
        stats["motion_frac_sum"] = stats["motion_frac_sum"] + 1.0
        st["stats"] = stats
        return eps, st
