"""Regenerate the pre-refactor golden run (``tests/golden/policies.npz``).

The golden file pins, per cache policy, the exact float32 latents and stat
counters produced by a fixed sampling run and a fixed serving trace.  It was
generated from the PRE-plugin-API monolithic ``CachedDiT`` (PR 4 tree), so
``tests/test_policies.py::test_golden_parity`` proves the plugin refactor is
a pure reorganization: every registered pre-existing policy must reproduce
these arrays bitwise.

Regenerate (only when intentionally changing policy numerics — which breaks
the "pure refactor" guarantee and should be called out in the PR):

    PYTHONPATH=src:. python tests/golden/generate.py

Determinism scope: bitwise reproducibility holds only for the jax version
CI pins (``.github/workflows/ci.yml``) on the same backend (x86-64 Linux).
XLA:CPU gemms are reduction-order deterministic per (shape, dtype), which is
all the fixed-shape runs below exercise.  The committed ``policies.npz``
predates the current pin, and four policies no longer match it bitwise
(ROADMAP, queue 3).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import build_dit
from repro.configs.base import FastCacheConfig
from repro.core import CachedDiT, POLICIES
from repro.diffusion import sample
from repro.serving import DiffusionRequest, DiffusionServingEngine

SAMPLE_STEPS = 6
SERVE_STEPS = 5          # serving-engine default plan budget

STAT_KEYS = ("blocks_computed", "blocks_skipped", "steps_reused",
             "motion_frac_sum")


def serving_trace():
    """Mixed-plan staggered trace: mid-flight admission, heterogeneous step
    budgets and guidance scales (1.0 exercises the unguided blend rows)."""
    return [DiffusionRequest(rid=0, label=1, seed=10, arrival_step=0,
                             num_steps=7, guidance_scale=4.0),
            DiffusionRequest(rid=1, label=2, seed=11, arrival_step=2,
                             num_steps=3, guidance_scale=1.0),
            DiffusionRequest(rid=2, label=3, seed=12, arrival_step=3,
                             num_steps=5, guidance_scale=2.0)]


def main() -> None:
    cfg, model, params = build_dit("dit-b2")
    img, ch = cfg.dit.image_size, cfg.dit.in_channels
    noise = jax.random.normal(jax.random.PRNGKey(123), (2, img, img, ch),
                              jnp.float32)
    out = {"policies": np.array(POLICIES)}
    for policy in POLICIES:
        runner = CachedDiT(model, FastCacheConfig(), policy=policy)
        x, state = sample(runner, params, jax.random.PRNGKey(0), batch=2,
                          labels=jnp.array([1, 2]), num_steps=SAMPLE_STEPS,
                          guidance_scale=4.0, x_init=noise)
        out[f"{policy}/sample/latents"] = np.asarray(x)
        for k in STAT_KEYS:
            out[f"{policy}/sample/{k}"] = np.asarray(state["stats"][k])

        runner = CachedDiT(model, FastCacheConfig(), policy=policy)
        eng = DiffusionServingEngine(runner, params, max_slots=2,
                                     num_steps=SERVE_STEPS, max_steps=7)
        done = eng.run(serving_trace())
        assert len(done) == 3
        for r in done:
            out[f"{policy}/serve/latents_rid{r.rid}"] = np.asarray(r.latents)
        cs = eng.cache_stats()
        out[f"{policy}/serve/headline"] = np.array(
            [cs["blocks_skipped"], cs["blocks_computed"],
             cs["steps_reused"]], np.float64)

    path = os.path.join(os.path.dirname(__file__), "policies.npz")
    np.savez_compressed(path, **out)
    print(f"wrote {path}: {len(out)} arrays, "
          f"{os.path.getsize(path) / 1024:.0f} KiB")


if __name__ == "__main__":
    main()
