"""The CachePolicy plugin API: registry-derived POLICIES, per-policy state
minimality, bitwise parity against the pre-refactor golden run (the
monolithic CachedDiT captured in tests/golden/policies.npz), tolerant
stats summaries, the SmoothCache-style layer-schedule policy, and the
front-door contract (a policy registered at runtime serves through both
engines with zero engine/sharding edits).

Run via ``make test-policies`` (CI job of the same name)."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core
from benchmarks.common import build_dit
from repro.configs import get_reduced
from repro.configs.base import FastCacheConfig
from repro.core import (CachedDiT, POLICIES, get_policy_class,
                        register, registered_policies, summarize_stats)
from repro.core.policies import base as policies_base
from repro.core.policies.fora import FORA
from repro.core.policies.smoothcache import (default_smooth_schedule,
                                             smooth_schedule_from_errors)
from repro.diffusion import sample
from repro.models import build_model
from repro.serving import DiffusionRequest, DiffusionServingEngine
from tests.conftest import assert_solo_replay_parity, f32_cfg
from tests.golden.generate import (SAMPLE_STEPS, SERVE_STEPS, STAT_KEYS,
                                   serving_trace)

pytestmark = pytest.mark.policies

GOLDEN = np.load(pathlib.Path(__file__).parent / "golden" / "policies.npz")


@pytest.fixture(scope="module")
def bench_dit():
    return build_dit("dit-b2")     # un-zeroed weights: policies diverge


@pytest.fixture(scope="module")
def reduced_dit():
    cfg = f32_cfg(get_reduced("dit-b2"))
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Registry / POLICIES
# ---------------------------------------------------------------------------

def test_policies_tuple_is_derived_from_registry():
    assert POLICIES == registered_policies()
    assert set(POLICIES) >= {"nocache", "fora", "teacache", "adacache",
                             "fbcache", "l2c", "fastcache", "smoothcache"}
    # module __getattr__: repro.core.POLICIES re-derives on access, so a
    # runtime registration shows up without editing any tuple
    @register("_probe")
    class Probe(FORA):
        pass
    try:
        assert "_probe" in repro.core.POLICIES
        assert get_policy_class("_probe") is Probe
    finally:
        del policies_base._REGISTRY["_probe"]
    assert "_probe" not in repro.core.POLICIES


def test_unknown_policy_raises_value_error(reduced_dit):
    """ValueError (not AssertionError — asserts vanish under python -O)
    listing the registered names."""
    cfg, model, params = reduced_dit
    with pytest.raises(ValueError, match="fastcache"):
        CachedDiT(model, FastCacheConfig(), policy="bogus")
    with pytest.raises(ValueError, match="gate_mode"):
        CachedDiT(model, FastCacheConfig(gate_mode="weird"))


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        register("fora")(type("Clash", (FORA,), {}))


# ---------------------------------------------------------------------------
# Satellite: per-policy state minimality
# ---------------------------------------------------------------------------

# exactly the buffers each policy owns (plus the standard stats block);
# the monolith allocated the UNION of these for every policy
EXPECTED_STATE = {
    "nocache": set(),
    "fora": {"prev_eps", "step_count", "have_cache"},
    "teacache": {"prev_tokens_in", "prev_eps", "tea_acc", "have_cache"},
    "adacache": {"prev_tokens_in", "prev_eps", "ada_skip_left",
                 "have_cache"},
    "fbcache": {"prev_h1", "prev_eps", "have_cache"},
    "l2c": set(),
    "fastcache": {"prev_tokens_in", "prev_hidden", "gate", "have_cache"},
    "smoothcache": {"prev_delta", "step_count", "have_cache"},
}

STD_STATS = {"blocks_computed", "blocks_skipped", "steps_reused",
             "motion_frac_sum", "blocks_run", "steps"}


@pytest.mark.parametrize("policy", POLICIES)
def test_init_state_is_minimal(reduced_dit, policy):
    cfg, model, params = reduced_dit
    runner = CachedDiT(model, FastCacheConfig(), policy=policy)
    state = runner.init_state(3)
    assert "stats" in state
    assert set(state["stats"]) == STD_STATS
    if policy in EXPECTED_STATE:
        assert set(state) - {"stats"} == EXPECTED_STATE[policy], policy
    # per-sample counters are (B,); reset_rows leaves batchmates alone
    assert all(state["stats"][k].shape == (3,)
               for k in STD_STATS - {"steps"})
    runner.reset_slot(state, 1)


def test_no_policy_carries_another_policies_buffers(reduced_dit):
    """The monolith's union allocation is gone: e.g. fora carries no chi^2
    trackers and no hidden stacks, nocache carries nothing at all."""
    cfg, model, params = reduced_dit
    fora = CachedDiT(model, FastCacheConfig(), policy="fora").init_state(2)
    assert "gate" not in fora and "prev_hidden" not in fora
    nc = CachedDiT(model, FastCacheConfig(), policy="nocache").init_state(2)
    assert set(nc) == {"stats"}
    # the big (L+1, B, N, D) payload stack exists ONLY where it is read
    for p in POLICIES:
        st = CachedDiT(model, FastCacheConfig(), policy=p).init_state(2)
        if p not in ("fastcache",):
            assert "prev_hidden" not in st, p


# ---------------------------------------------------------------------------
# Satellite: bitwise parity with the pre-refactor golden run
# ---------------------------------------------------------------------------

GOLDEN_POLICIES = tuple(str(p) for p in GOLDEN["policies"])


@pytest.mark.parametrize("policy", GOLDEN_POLICIES)
def test_golden_sample_parity(bench_dit, policy):
    """Every pre-existing policy reproduces the monolith's sample() run
    bitwise — latents AND per-sample stat counters."""
    cfg, model, params = bench_dit
    img, ch = cfg.dit.image_size, cfg.dit.in_channels
    noise = jax.random.normal(jax.random.PRNGKey(123), (2, img, img, ch),
                              jnp.float32)
    runner = CachedDiT(model, FastCacheConfig(), policy=policy)
    x, state = sample(runner, params, jax.random.PRNGKey(0), batch=2,
                      labels=jnp.array([1, 2]), num_steps=SAMPLE_STEPS,
                      guidance_scale=4.0, x_init=noise)
    np.testing.assert_array_equal(np.asarray(x),
                                  GOLDEN[f"{policy}/sample/latents"])
    for k in STAT_KEYS:
        np.testing.assert_array_equal(np.asarray(state["stats"][k]),
                                      GOLDEN[f"{policy}/sample/{k}"],
                                      err_msg=f"{policy}/{k}")


@pytest.mark.parametrize("policy", GOLDEN_POLICIES)
def test_golden_serving_parity(bench_dit, policy):
    """The serving engine reproduces the monolith's mixed-plan staggered
    trace bitwise through the plugin path — per-request latents and the
    headline cache counters."""
    cfg, model, params = bench_dit
    runner = CachedDiT(model, FastCacheConfig(), policy=policy)
    eng = DiffusionServingEngine(runner, params, max_slots=2,
                                 num_steps=SERVE_STEPS, max_steps=7)
    done = eng.run(serving_trace())
    assert len(done) == 3
    for r in done:
        np.testing.assert_array_equal(
            np.asarray(r.latents), GOLDEN[f"{policy}/serve/latents_rid{r.rid}"],
            err_msg=f"{policy} rid={r.rid}")
    cs = eng.cache_stats()
    np.testing.assert_array_equal(
        np.array([cs["blocks_skipped"], cs["blocks_computed"],
                  cs["steps_reused"]], np.float64),
        GOLDEN[f"{policy}/serve/headline"], err_msg=policy)


# ---------------------------------------------------------------------------
# Satellite: summarize_stats tolerates any policy's state pytree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", POLICIES)
def test_summarize_stats_over_every_registered_policy(reduced_dit, policy):
    cfg, model, params = reduced_dit
    runner = CachedDiT(model, FastCacheConfig(), policy=policy)
    s = summarize_stats(runner.init_state(2))
    assert s["steps"] == 0.0 and s["block_cache_ratio"] == 0.0
    assert runner.stats(runner.init_state(2)) == s


def test_summarize_stats_missing_keys_return_zero():
    """A future policy that tracks only SOME counters (or none) must not
    KeyError the summary."""
    s = summarize_stats({"stats": {}})
    assert s["blocks_computed"] == 0.0 and s["block_cache_ratio"] == 0.0
    assert "per_sample" not in s
    s = summarize_stats({"stats": {
        "blocks_skipped": jnp.array([3.0, 1.0]),
        "steps": jnp.asarray(2.0)}})
    assert s["blocks_skipped"] == 2.0          # batch mean
    assert s["blocks_computed"] == 0.0         # absent -> 0.0, no KeyError
    assert s["block_cache_ratio"] == 1.0
    assert s["per_sample"] == {"blocks_skipped": [3.0, 1.0]}
    assert summarize_stats({})["steps"] == 0.0  # no stats block at all


# ---------------------------------------------------------------------------
# smoothcache: the SmoothCache-style layer-schedule policy
# ---------------------------------------------------------------------------

def test_smoothcache_schedule_helpers():
    sched = default_smooth_schedule(3, interval=2, table_steps=8)
    assert sched.shape == (3, 8)
    assert not sched[:, 0].any() and sched[:, 1].all()
    err = jnp.array([[0.0, 0.01, 0.5], [0.0, 0.2, 0.01]])
    cal = smooth_schedule_from_errors(err, threshold=0.05)
    assert not cal[:, 0].any()                 # step 0 always computes
    assert bool(cal[0, 1]) and not bool(cal[1, 1])


def test_smoothcache_follows_its_schedule(reduced_dit):
    """With the default every-other-step schedule, half the steps after
    warm-up reuse every layer's cached residual."""
    cfg, model, params = reduced_dit
    runner = CachedDiT(model, FastCacheConfig(), policy="smoothcache")
    img, ch = cfg.dit.image_size, cfg.dit.in_channels
    x = jax.random.normal(jax.random.PRNGKey(1), (2, img, img, ch))
    state = runner.init_state(2)
    step = jax.jit(runner.step)
    for t in range(6):
        eps, state = step(params, state, x, jnp.full((2,), 25),
                          jnp.array([1, 2]))
    s = summarize_stats(state)
    # steps 1,3,5 reuse (schedule), 0,2,4 compute: ratio == 0.5
    assert s["block_cache_ratio"] == 0.5, s
    with pytest.raises(ValueError, match="layer rows"):
        CachedDiT(model, FastCacheConfig(), policy="smoothcache",
                  smooth_schedule=jnp.zeros((7, 4), bool))


def test_smoothcache_custom_schedule_via_front_door(reduced_dit):
    """The schedule kwarg reaches the policy through CachedDiT's generic
    **policy_kwargs passthrough — no shell edit was needed for it."""
    cfg, model, params = reduced_dit
    sched = default_smooth_schedule(cfg.num_layers, interval=3)
    runner = CachedDiT(model, FastCacheConfig(), policy="smoothcache",
                       smooth_schedule=sched)
    img, ch = cfg.dit.image_size, cfg.dit.in_channels
    x = jax.random.normal(jax.random.PRNGKey(1), (1, img, img, ch))
    state = runner.init_state(1)
    step = jax.jit(runner.step)
    for t in range(6):
        eps, state = step(params, state, x, jnp.full((1,), 25),
                          jnp.array([1]))
    # interval 3: steps 1,2,4,5 reuse; 0,3 compute
    assert summarize_stats(state)["block_cache_ratio"] == pytest.approx(4 / 6)


# ---------------------------------------------------------------------------
# Front door: a policy registered at runtime serves with zero engine edits
# ---------------------------------------------------------------------------

def test_runtime_registered_policy_serves_front_door(reduced_dit):
    """Acceptance: adding a cache method is ONE registration — the shell,
    the serving engine, slot reset, per-request counters and the solo
    bitwise-replay contract all pick it up with no serving/ or
    distributed/ edits (the sharded engine shares this path via the opaque
    state walker, exercised per-policy in test_sharded_serving.py)."""
    cfg, model, params = reduced_dit

    @register("_everyother")
    class EveryOther(FORA):
        """FORA at interval 2, under a fresh name and registered live."""
        def __init__(self, model, fc, fc_params, **kw):
            kw.pop("fora_interval", None)
            super().__init__(model, fc, fc_params, fora_interval=2, **kw)

    try:
        runner = CachedDiT(model, FastCacheConfig(), policy="_everyother")
        eng = DiffusionServingEngine(runner, params, max_slots=2,
                                     num_steps=3)
        trace = [DiffusionRequest(rid=0, label=1, seed=1, arrival_step=0),
                 DiffusionRequest(rid=1, label=2, seed=2, arrival_step=1)]
        done = eng.run(trace)
        assert len(done) == 2
        assert_solo_replay_parity(eng, model, params, "_everyother", done)
        # interval 2 over 3 steps reuses step 1, on both CFG rows
        assert all(r.cache["steps_reused"] == 2.0 for r in done)
    finally:
        del policies_base._REGISTRY["_everyother"]
