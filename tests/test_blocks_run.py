"""``blocks_run``: the DiT blocks the device executed over each row's
tokens, counted by hand against the per-row decisions ``blocks_computed``
and ``blocks_skipped``.  A gated block runs for the whole batch unless
every row skips it; a mixed warm/cold step runs the full forward on top of
the gated path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import FastCacheConfig
from repro.core import CachedDiT
from repro.kernels import ref as kernel_ref
from repro.models import build_model
from repro.serving import DiffusionRequest, DiffusionServingEngine
from tests.conftest import f32_cfg

B = 3


@pytest.fixture(scope="module")
def dit():
    cfg = f32_cfg(get_reduced("dit-b2"))
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def inputs(model):
    img, ch = model.cfg.dit.image_size, model.cfg.dit.in_channels
    x = jax.random.normal(jax.random.PRNGKey(3), (B, img, img, ch))
    return x, jnp.full((B,), 500, jnp.int32), jnp.arange(B, dtype=jnp.int32)


def counts(state):
    s = state["stats"]
    return {k: np.asarray(s[k]).tolist()
            for k in ("blocks_run", "blocks_computed", "blocks_skipped")}


def gate_caching(rows):
    """A stand-in for the fused gate that caches exactly ``rows``."""
    def gate(xm, prev_m, prev_om, w, b, sigma2, eligible, **_):
        zero = jnp.zeros((xm.shape[0],), jnp.float32)
        return xm, jnp.asarray(rows), zero, zero
    return gate


def step(runner, params, state, model):
    x, t, labels = inputs(model)
    return runner.step(params, state, x, t, labels)[1]


@pytest.mark.parametrize("case, cache, mixed, want", [
    # (blocks_run, blocks_computed, blocks_skipped) per row, in units of L,
    # added by the step after a cold first step
    ("all rows cache", [True, True, True], False,
     ([0, 0, 0], [0, 0, 0], [1, 1, 1])),
    ("one row recomputes", [True, False, True], False,
     ([1, 1, 1], [0, 1, 0], [1, 0, 1])),
    ("mixed, all warm rows cache", [True, True, True], True,
     ([1, 1, 1], [0, 1, 0], [1, 0, 1])),
    ("mixed, a warm row recomputes", [False, True, True], True,
     ([2, 2, 2], [1, 1, 0], [0, 0, 1])),
])
def test_fastcache_hand_counts(dit, monkeypatch, case, cache, mixed, want):
    model, params = dit
    runner = CachedDiT(model, FastCacheConfig(use_fused_gate=False),
                       policy="fastcache")
    L = runner.L
    state = step(runner, params, runner.init_state(B), model)
    cold = counts(state)
    assert cold == {"blocks_run": [L] * B, "blocks_computed": [L] * B,
                    "blocks_skipped": [0] * B}, "cold step"
    if mixed:                          # row 1 re-admitted: cold again
        state = runner.reset_slot(state, jnp.array([1]))
    monkeypatch.setattr(kernel_ref, "fused_gate", gate_caching(cache))
    after = counts(step(runner, params, state, model))
    got = tuple([(a - c) / L for a, c in zip(after[k], cold[k])]
                for k in ("blocks_run", "blocks_computed", "blocks_skipped"))
    assert got == want, case


def test_nocache_runs_every_block(dit):
    model, params = dit
    runner = CachedDiT(model, FastCacheConfig(), policy="nocache")
    state = runner.init_state(B)
    for _ in range(2):
        state = step(runner, params, state, model)
    assert counts(state) == {"blocks_run": [2 * runner.L] * B,
                             "blocks_computed": [2 * runner.L] * B,
                             "blocks_skipped": [0] * B}


@pytest.mark.parametrize("skip, probe, run", [
    ([True, True, True], 0.0, 0.0),        # every row reuses its eps
    ([True, False, True], 0.0, 1.0),       # one row recomputes: all run
    ([True, True, True], 1.0, 0.0),        # fbcache's probe block only
    ([False, False, True], 1.0, 1.0),      # the probe, then the stack
])
def test_masked_step_hand_counts(dit, skip, probe, run):
    model, params = dit
    runner = CachedDiT(model, FastCacheConfig(), policy="fora")
    L = runner.L
    x, t, labels = inputs(model)
    x_in = model.tokens_in(params, x)
    c = model.conditioning(params, t, labels)
    state = runner.init_state(B)
    _, st = runner.impl.masked_step(params, state, x_in, c,
                                    jnp.asarray(skip),
                                    computed_on_skip=probe)
    got = counts(st)
    ran = probe + run * L
    assert got["blocks_run"] == [ran] * B
    assert got["blocks_computed"] == [probe if s else L for s in skip]


def test_engine_counts_blocks_run_for_active_rows(dit):
    """Through the engine: ``acc`` and ``cache_stats`` carry ``blocks_run``
    for active rows, never fewer than the blocks the rows computed."""
    model, params = dit
    runner = CachedDiT(model, FastCacheConfig(), policy="fastcache")
    eng = DiffusionServingEngine(runner, params, max_slots=2, num_steps=4,
                                 guidance_scale=4.0)
    done = eng.run([DiffusionRequest(rid=i, label=i, seed=i,
                                     arrival_step=2 * i) for i in range(3)])
    assert len(done) == 3
    cs = eng.cache_stats()
    assert cs["blocks_run"] >= cs["blocks_computed"] > 0
    # every request's steps ran every block on the cold first step at least
    assert all(r.cache["blocks_run"] >= 2 * runner.L for r in done)
