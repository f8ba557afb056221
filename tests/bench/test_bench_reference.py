"""bench/reference.py against the serving program's own ``sample()`` at a
small size on the CPU, both in float32 from the same weights: the plain
reference and the program implement the same sampler, cache policy and
token merging, so they agree to float32 rounding."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference, weights  # noqa: E402
from bench.families.dit import model_config  # noqa: E402

SMALL = {"model": "dit-xl2", "depth": 3, "hidden_size": 64, "num_heads": 4,
         "patch_size": 2, "input_size": 8, "in_channels": 4,
         "mlp_ratio": 4.0, "num_classes": 10, "learn_sigma": True,
         "dtype": "float32", "slots": 2}

CASES = {
    "nocache": ("nocache", {}),
    "fastcache": ("fastcache", {}),
    "fastcache_merge": ("fastcache", {"merge_enabled": True,
                                      "merge_ratio": 0.5,
                                      "merge_window": 16}),
}


def program_sample(cfg, params, noise, labels, steps, guidance):
    from repro.configs.base import FastCacheConfig
    from repro.core import CachedDiT
    from repro.diffusion import sample
    from repro.models import build_model
    model = build_model(model_config(cfg))
    runner = CachedDiT(model, FastCacheConfig(use_fused_gate=False,
                                              **cfg["fastcache"]),
                       policy=cfg["policy"])
    x, _ = sample(runner, params, jax.random.PRNGKey(0), batch=len(labels),
                  labels=jnp.asarray(labels), num_steps=steps,
                  guidance_scale=jnp.asarray(guidance),
                  x_init=jnp.asarray(noise))
    return np.asarray(x)


@pytest.mark.parametrize("case", sorted(CASES))
def test_reference_matches_program_in_float32(case):
    policy, fc = CASES[case]
    cfg = dict(SMALL, policy=policy, fastcache=fc)
    d = weights.dims_of(cfg)
    params = weights.make_params(d, 3, dtype="float32")
    seeds, labels, guidance, steps = [11, 12, 13], [1, 5, 9], [4.0, 1.0, 4.0], 10
    noise = np.stack([weights.request_noise(s, d) for s in seeds])
    got = program_sample(cfg, params, noise, labels, steps, guidance)
    want, first = reference.sample(reference.to_f32(params), d,
                                   reference.algo_of(cfg), seeds, labels,
                                   steps, guidance)
    assert first.shape == want.shape == got.shape
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    # float32 against float32 at the highest precision, through 10 steps
    # that grow the latents by 1/sqrt(alpha_bar) ~ 150 (a flipped cache or
    # merge decision would read above 1e-2)
    assert err < 1e-4, err


def test_control_is_farther_than_reference_rounding():
    """The float8 control drifts from float32 far more than float32
    rounding does."""
    cfg = dict(SMALL, policy="nocache", fastcache={})
    d = weights.dims_of(cfg)
    p32 = reference.to_f32(weights.make_params(d, 4, dtype="float32"))
    a = reference.algo_of(cfg)
    ref, _ = reference.sample(p32, d, a, [1, 2], [0, 3], 10, [4.0, 4.0])
    ctl, _ = reference.sample(p32, d, a, [1, 2], [0, 3], 10, [4.0, 4.0],
                              quant=True)
    err = np.linalg.norm(ctl - ref) / np.linalg.norm(ref)
    assert err > 1e-2, err
