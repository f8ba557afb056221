"""Shared benchmark utilities: reduced DiT variants (CPU-scale stand-ins for
the paper's DiT-S/B/L/XL), timing, and quality proxies.

Quality metrics: the paper reports FID / t-FID against real data; offline on
CPU we report (a) relative L2 error of generated latents vs the exact
(nocache) sampler — the direct measure of cache-induced deviation — and (b) a
Frechet distance between Gaussian fits of latent feature vectors
("fid_proxy"), directionally comparable to FID deltas between methods.
"""
from __future__ import annotations

import time
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FastCacheConfig, ModelConfig
from repro.configs.dit import _dit
from repro.core import CachedDiT, summarize_stats
from repro.diffusion import sample
from repro.models import build_model
from repro.models.dit import unzero_params

# CPU-scale stand-ins mirroring the paper's depth/width ladder (Table 4)
BENCH_DITS: Dict[str, ModelConfig] = {
    "dit-s2": _dit("bench-dit-s2", 3, 96, 4),
    "dit-b2": _dit("bench-dit-b2", 4, 128, 4),
    "dit-l2": _dit("bench-dit-l2", 6, 160, 4),
    "dit-xl2": _dit("bench-dit-xl2", 7, 192, 4),
}
for k in list(BENCH_DITS):
    import dataclasses
    BENCH_DITS[k] = BENCH_DITS[k].replace(
        dtype="float32",
        dit=dataclasses.replace(BENCH_DITS[k].dit, num_classes=10,
                                image_size=16))


def build_dit(name: str):
    cfg = BENCH_DITS[name]
    model = build_model(cfg)
    params = unzero_params(model.init(jax.random.PRNGKey(0)),
                           jax.random.PRNGKey(1))
    return cfg, model, params


def timed_sample(model, params, fc: FastCacheConfig, policy: str, *,
                 batch: int = 2, steps: int = 12, guidance: float = 4.0,
                 seed: int = 0, repeats: int = 2,
                 **runner_kw) -> Tuple[jax.Array, Dict]:
    runner = CachedDiT(model, fc, policy=policy, **runner_kw)
    key = jax.random.PRNGKey(seed)
    # warmup (compile)
    x, state = sample(runner, params, key, batch=batch, num_steps=steps,
                      guidance_scale=guidance)
    jax.block_until_ready(x)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        x, state = sample(runner, params, key, batch=batch, num_steps=steps,
                          guidance_scale=guidance)
        jax.block_until_ready(x)
        best = min(best, time.perf_counter() - t0)
    stats = summarize_stats(state)
    stats["time_s"] = best
    stats["us_per_step"] = best / steps * 1e6
    return x, stats


def rel_err(x, ref) -> float:
    return float(jnp.linalg.norm(x - ref) / (jnp.linalg.norm(ref) + 1e-9))


def frechet_proxy(x, ref) -> float:
    """Frechet distance between Gaussian fits of latent feature vectors
    (samples = all spatial positions of all images)."""
    def stats(a):
        f = np.asarray(a).reshape(-1, a.shape[-1]).astype(np.float64)
        return f.mean(0), np.cov(f, rowvar=False)

    mu1, c1 = stats(x)
    mu2, c2 = stats(ref)
    diff = float(((mu1 - mu2) ** 2).sum())
    try:
        import scipy.linalg
        covmean = scipy.linalg.sqrtm(c1 @ c2)
        if np.iscomplexobj(covmean):
            covmean = covmean.real
        tr = float(np.trace(c1 + c2 - 2.0 * covmean))
    except Exception:
        tr = float(np.trace(c1 + c2))
    return diff + max(tr, 0.0)
