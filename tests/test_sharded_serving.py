"""Sharded multi-device diffusion serving: `ShardedDiffusionEngine` on a
(data, model) mesh must be **bitwise** identical to the single-device
`DiffusionServingEngine` for every cache policy — including mid-flight
admission and straggler warm-up — and the donated serve_step must keep
cache state device-resident (no per-step host round-trip).

Full multi-device coverage needs 8 virtual CPU devices:

    make test-sharded        # XLA_FLAGS=--xla_force_host_platform_device_count=8

On a single device the multi-device cases skip; the (1,1)-mesh parity,
donation and scheduler tests still run in the tier-1 suite.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_reduced
from repro.configs.base import FastCacheConfig
from repro.core import CachedDiT, POLICIES
from repro.distributed.sharding import (ShardingCtx, make_rules,
                                        serve_state_specs,
                                        serve_state_shardings)
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.serving import (DiffusionRequest, DiffusionServingEngine,
                           ShardedDiffusionEngine, make_serving_mesh,
                           poisson_trace)
from tests.conftest import assert_solo_replay_parity, f32_cfg

pytestmark = [pytest.mark.serving, pytest.mark.distributed]

STEPS = 4

multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(run via `make test-sharded`)")


@pytest.fixture(scope="module")
def dit():
    cfg = f32_cfg(get_reduced("dit-b2"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _staggered_trace():
    """Mid-flight admission AND straggler warm-up AND heterogeneous
    sampling plans: r0/r1 start (different step budgets + guidance), r2-r4
    queue and are admitted next to warm residents running different plans
    once slots free (r3 keeps the engine defaults)."""
    return [DiffusionRequest(rid=0, label=1, seed=10, arrival_step=0,
                             num_steps=4, guidance_scale=4.0),
            DiffusionRequest(rid=1, label=2, seed=11, arrival_step=1,
                             num_steps=2, guidance_scale=1.0),
            DiffusionRequest(rid=2, label=3, seed=12, arrival_step=2,
                             num_steps=3, guidance_scale=2.0),
            DiffusionRequest(rid=3, label=4, seed=13, arrival_step=3),
            DiffusionRequest(rid=4, label=5, seed=14, arrival_step=3,
                             num_steps=3, guidance_scale=1.0)]


def _base(model, params, policy, *, slots=4):
    runner = CachedDiT(model, FastCacheConfig(), policy=policy)
    return DiffusionServingEngine(runner, params, max_slots=slots,
                                  num_steps=STEPS)


def _sharded(model, params, policy, *, topo, slots=4, async_admission=True):
    runner = CachedDiT(model, FastCacheConfig(), policy=policy)
    return ShardedDiffusionEngine(runner, params, max_slots=slots,
                                  num_steps=STEPS,
                                  mesh=make_serving_mesh(*topo),
                                  async_admission=async_admission)


def _run_latents(eng):
    done = eng.run(_staggered_trace())
    assert len(done) == 5
    return {r.rid: np.asarray(r.latents) for r in done}


def _assert_same_serving(base_eng, sharded_eng):
    """Bitwise parity of latents, headline cache stats AND the full
    per-slot cache/gate state (payloads, chi^2 trackers, counters, plan
    tables, request-scoped accumulators) — the state comparison keeps this
    meaningful even where latents alone would be insensitive to caching
    decisions."""
    a = _run_latents(base_eng)
    b = _run_latents(sharded_eng)
    for rid in a:
        np.testing.assert_array_equal(a[rid], b[rid], err_msg=f"rid={rid}")
    sa, sb = base_eng.cache_stats(), sharded_eng.cache_stats()
    for k in ("blocks_skipped", "blocks_computed", "steps_reused",
              "block_cache_ratio", "engine_steps", "model_steps"):
        assert sa[k] == sb[k], (k, sa[k], sb[k])
    tree_a = (base_eng.state, base_eng.plan, base_eng.slot_acc)
    tree_b = (sharded_eng.state, sharded_eng.plan, sharded_eng.slot_acc)
    for (path, la), lb in zip(jax.tree.flatten_with_path(tree_a)[0], jax.tree.leaves(tree_b)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb),
            err_msg=f"state leaf {jax.tree_util.keystr(path)}")


# ---------------------------------------------------------------------------
# kind="serve" sharding rules + state sharding trees
# ---------------------------------------------------------------------------

def test_serve_rules_shard_slots_over_data():
    r = make_rules("serve")
    assert r["slot"] == ("data",)
    assert r["act_batch"] == ("data",)
    assert r["layers"] is None          # layer-stacked trackers replicated
    # weights stay tensor-parallel over `model`
    assert r["ffn"] == ("model",) and r["heads"] == ("model",)
    # non-serve kinds leave slot rows unmapped
    assert make_rules("train")["slot"] is None


@pytest.mark.parametrize("policy", POLICIES)
def test_serve_state_specs_cover_every_leaf(dit, policy):
    """The opaque-pytree walker covers EVERY registered policy's state: it
    derives each leaf's spec from rank/extents alone (no state keys), so a
    new policy module shards without touching distributed/sharding.py."""
    cfg, model, params = dit
    runner = CachedDiT(model, FastCacheConfig(), policy=policy)
    state = runner.init_state(4)
    ctx = ShardingCtx(make_host_mesh(),
                      make_rules("serve"))
    specs = serve_state_specs(state, ctx, batch=4, layers=runner.L)
    flat_state = jax.tree.leaves(state)
    flat_specs = jax.tree.leaves(specs,
                                 is_leaf=lambda x: isinstance(x, P))
    assert len(flat_state) == len(flat_specs)
    for leaf, spec in zip(flat_state, flat_specs):
        assert len(spec) == leaf.ndim, (leaf.shape, spec)
    sh = serve_state_shardings(state, ctx, batch=4, layers=runner.L)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, state)) == \
        jax.tree.structure(jax.tree.map(lambda _: 0, sh))


def test_slot_axis_rank_rules(dit):
    """The walker's rank/leading-axis contract: leading batch dim -> slot;
    layer-stacked (L or L+1 leading, batch second) -> slot on axis 1 (the
    layer rule wins even when L == batch); no batch extent -> replicated."""
    from repro.distributed.sharding import _slot_axis
    assert _slot_axis((8,), 8, 2) == 0
    assert _slot_axis((8, 16, 128), 8, 2) == 0
    assert _slot_axis((2, 8), 8, 2) == 1          # (L, B) trackers
    assert _slot_axis((3, 8, 16, 128), 8, 2) == 1  # (L+1, B, N, D) payloads
    assert _slot_axis((4, 4), 4, 4) == 1          # L == batch: layer rule
    assert _slot_axis((), 8, 2) is None
    assert _slot_axis((5, 7), 8, 2) is None       # no batch extent


def test_serve_plan_specs_shard_slot_rows():
    from repro.distributed.sharding import serve_plan_specs
    ctx = ShardingCtx(make_host_mesh(),
                      make_rules("serve"))
    plan = {"ts": jnp.zeros((4, 8), jnp.int32),
            "ts_prev": jnp.zeros((4, 8), jnp.int32),
            "guidance": jnp.zeros((4,), jnp.float32)}
    specs = serve_plan_specs(plan, ctx)
    assert set(specs) == {"ts", "ts_prev", "guidance"}
    # slot dim carries the "slot" logical axis -> `data` on serve meshes
    # (this (1,1) mesh collapses it, but the spec rank must match)
    assert all(len(specs[k]) == plan[k].ndim for k in specs)


# ---------------------------------------------------------------------------
# Satellite: donated serve_step — cache state never round-trips the host
# ---------------------------------------------------------------------------

def test_serve_step_donates_state_no_host_transfer(dit):
    cfg, model, params = dit
    eng = _base(model, params, "fastcache", slots=2)
    eng.add_request(DiffusionRequest(rid=0, label=1, seed=5))
    eng.step()                          # compile outside the guard
    old_state_leaves = jax.tree.leaves(eng.state)
    old_x, old_acc = eng.x, dict(eng.acc)
    # no slot completes on this step, so nothing may touch the host
    with jax.transfer_guard_device_to_host("disallow"):
        eng.step()
    # donation: the previous step's buffers were aliased, not copied
    assert all(leaf.is_deleted() for leaf in old_state_leaves)
    assert old_x.is_deleted()
    assert all(v.is_deleted() for v in old_acc.values())


def test_admission_is_donated_too(dit):
    cfg, model, params = dit
    eng = _base(model, params, "fastcache", slots=2)
    eng.add_request(DiffusionRequest(rid=0, label=1, seed=5))
    eng.step()
    old_state_leaves = jax.tree.leaves(eng.state)
    with jax.transfer_guard_device_to_host("disallow"):
        assert eng.add_request(DiffusionRequest(rid=1, label=2, seed=6))
    assert all(leaf.is_deleted() for leaf in old_state_leaves)


# ---------------------------------------------------------------------------
# (1,1)-mesh parity: the sharded runtime is a pure refactor of the math
# ---------------------------------------------------------------------------

def test_sharded_1x1_matches_base_bitwise(dit):
    cfg, model, params = dit
    _assert_same_serving(_base(model, params, "fastcache"),
                         _sharded(model, params, "fastcache", topo=(1, 1)))


def test_sharded_no_cfg_fast_path_matches_base(dit):
    """cfg_rows=False rides the sharded runtime unchanged: single-row
    slots (state batch S), bitwise-equal latents to the single-device
    fast-path engine."""
    cfg, model, params = dit
    mk = lambda: CachedDiT(model, FastCacheConfig(), policy="fastcache")
    base = DiffusionServingEngine(mk(), params, max_slots=4,
                                  num_steps=STEPS, guidance_scale=1.0,
                                  cfg_rows=False)
    sh = ShardedDiffusionEngine(mk(), params, max_slots=4, num_steps=STEPS,
                                guidance_scale=1.0, cfg_rows=False,
                                mesh=make_serving_mesh(1, 1))
    assert sh.rows_per_slot == 1
    assert sh.state["have_cache"].shape == (4,)
    trace = [DiffusionRequest(rid=i, label=i, seed=20 + i, arrival_step=i)
             for i in range(5)]
    a = {r.rid: np.asarray(r.latents) for r in base.run(list(trace))}
    b = {r.rid: np.asarray(r.latents) for r in sh.run(list(trace))}
    for rid in a:
        np.testing.assert_array_equal(a[rid], b[rid], err_msg=f"rid={rid}")


def test_sharded_merge_1x1_matches_base_and_solo(dit):
    """Token compression rides the sharded runtime unchanged: merge-on
    (r=0.5) sharded serving is bitwise-equal to the single-device merge-on
    engine — including the reducer's per-slot saliency rows in the state
    pytree — and every finished request matches its merge-on solo replay,
    mid-flight admission included."""
    cfg, model, params = dit
    fc = FastCacheConfig(merge_enabled=True, merge_ratio=0.5,
                         merge_window=8)
    mk = lambda: CachedDiT(model, fc, policy="fastcache")
    assert mk().reducer is not None
    base = DiffusionServingEngine(mk(), params, max_slots=4,
                                  num_steps=STEPS)
    sh = ShardedDiffusionEngine(mk(), params, max_slots=4, num_steps=STEPS,
                                mesh=make_serving_mesh(1, 1))
    assert "tokred" in sh.state
    _assert_same_serving(base, sh)
    done = sh.run(_staggered_trace())
    assert_solo_replay_parity(sh, model, params, "fastcache", done, fc=fc)


@multi_device
def test_sharded_merge_parity_data4(dit):
    """Merge-on parity on the real (data=4) mesh: the reducer's
    prev_full/have_prev rows shard over `data` with the other slot state
    and the served latents still match the single-device engine bitwise."""
    cfg, model, params = dit
    fc = FastCacheConfig(merge_enabled=True, merge_ratio=0.5,
                         merge_window=8)
    mk = lambda: CachedDiT(model, fc, policy="fastcache")
    base = DiffusionServingEngine(mk(), params, max_slots=4,
                                  num_steps=STEPS)
    sh = ShardedDiffusionEngine(mk(), params, max_slots=4, num_steps=STEPS,
                                mesh=make_serving_mesh(4, 1))
    assert sh.state["tokred"]["prev_full"].sharding.spec[0] == "data"
    _assert_same_serving(base, sh)


def test_async_admission_matches_sync(dit):
    cfg, model, params = dit
    a = _run_latents(_sharded(model, params, "fastcache", topo=(1, 1),
                              async_admission=True))
    b = _run_latents(_sharded(model, params, "fastcache", topo=(1, 1),
                              async_admission=False))
    for rid in a:
        np.testing.assert_array_equal(a[rid], b[rid])


def test_admission_noise_lands_with_slot_spec(dit):
    cfg, model, params = dit
    eng = _sharded(model, params, "fastcache", topo=(1, 1))
    # one slot's row spec = the latent spec minus the slot axis
    assert eng._slot_row_sh.spec == P(*eng._x_sh.spec[1:])
    req = DiffusionRequest(rid=0, label=1, seed=5)
    staged = eng._staged_noise(req)
    assert staged.sharding == eng._slot_row_sh
    eng.add_request(req)
    assert eng.x.sharding.spec == eng._x_sh.spec  # layout undisturbed


# ---------------------------------------------------------------------------
# Multi-device: bitwise parity per policy on the 8-virtual-device mesh
# ---------------------------------------------------------------------------

@multi_device
@pytest.mark.parametrize("policy", POLICIES)
def test_sharded_parity_data4(dit, policy):
    """(data=4, model=1): slots and all per-slot cache/gate/stat rows —
    including the (S, max_steps) sampling-plan tables — shard 4-way;
    latents and cache-ratio stats must match the single-device engine
    bitwise, mid-flight admissions of HETEROGENEOUS plans included (the
    shared trace mixes 2/3/4-step budgets and guidance 1.0/2.0/4.0)."""
    cfg, model, params = dit
    _assert_same_serving(_base(model, params, policy),
                         _sharded(model, params, policy, topo=(4, 1)))


@multi_device
@pytest.mark.parametrize("policy", POLICIES)
def test_sharded_mixed_plans_match_solo_replay(dit, policy):
    """Tentpole acceptance on the mesh: every request of a mixed-plan batch
    served by the (4, 1) sharded engine is bitwise-equal to a solo
    ``sample()`` replay under its own resolved (num_steps,
    guidance_scale)."""
    cfg, model, params = dit
    eng = _sharded(model, params, policy, topo=(4, 1))
    done = eng.run(_staggered_trace())
    assert len(done) == 5
    # per-request budgets resolved (rid 3 fell back to the engine default)
    assert {r.rid: r.num_steps for r in done} == \
        {0: 4, 1: 2, 2: 3, 3: STEPS, 4: 3}
    assert_solo_replay_parity(eng, model, params, policy, done)


@multi_device
def test_model_axis_numerics_guard(dit):
    """model>1 meshes auto-run the startup numerics self-check.  On this
    jax/XLA CPU version the partitioner miscompiles the serve_step for any
    model>1 topology (NaNs / double-counted reductions observed during
    bring-up), so the engine must refuse to serve rather than emit garbage
    — on a backend that partitions correctly this constructs fine and the
    engine serves validated."""
    cfg, model, params = dit
    try:
        eng = _sharded(model, params, "fastcache", topo=(4, 2))
    except RuntimeError as e:
        assert "numerics self-check" in str(e)
        return
    # backend partitions model>1 correctly: the validated engine must
    # still match the single-device run end to end
    _assert_same_serving(_base(model, params, "fastcache"), eng)


@multi_device
@pytest.mark.parametrize("topo", [(4, 1), (2, 2)])
def test_fused_kernels_run_per_shard(dit, topo):
    """The compiler cannot partition a Pallas kernel, so on a mesh the
    fused gate and the token-merge kernels run per shard of their leading
    axis (``kernels/ops._rowwise``).  Served through them (interpret mode
    here), the mesh must serve what the single-device engine serves."""
    cfg, model, params = dit
    fc = FastCacheConfig(use_fused_gate=True, merge_enabled=True,
                         merge_ratio=0.5, merge_window=16)
    base = DiffusionServingEngine(CachedDiT(model, fc, policy="fastcache"),
                                  params, max_slots=4, num_steps=STEPS)
    eng = ShardedDiffusionEngine(CachedDiT(model, fc, policy="fastcache"),
                                 params, max_slots=4, num_steps=STEPS,
                                 mesh=make_serving_mesh(*topo))
    a, b = _run_latents(base), _run_latents(eng)
    for rid in a:
        np.testing.assert_allclose(a[rid], b[rid], rtol=1e-5, atol=1e-5,
                                   err_msg=f"rid={rid}")
    sa, sb = base.cache_stats(), eng.cache_stats()
    assert sa["blocks_skipped"] == sb["blocks_skipped"]
    assert sa["blocks_computed"] == sb["blocks_computed"]


@multi_device
def test_state_is_actually_sharded(dit):
    cfg, model, params = dit
    eng = _sharded(model, params, "fastcache", topo=(4, 1))
    # CFG doubles the slot rows: 8 state rows over data=4
    assert eng.state["prev_hidden"].sharding.spec[1] == "data"
    assert eng.state["gate"].sigma2.sharding.spec[1] == "data"
    assert eng.state["stats"]["blocks_skipped"].sharding.spec[0] == "data"
    assert eng.x.sharding.spec[0] == "data"
    # sampling-plan tables shard with the slot rows over `data`
    assert eng.plan["ts"].sharding.spec[0] == "data"
    assert eng.plan["ts_prev"].sharding.spec[0] == "data"
    assert eng.plan["guidance"].sharding.spec[0] == "data"
    assert all(v.sharding.spec[0] == "data"
               for v in eng.slot_acc.values())
    assert eng.topology() == {"data": 4, "model": 1, "devices": 4}


def test_admission_plan_rows_land_with_table_row_spec(dit):
    """Plan rows ride the same per-slot device_put mechanism as the
    admission noise: staged with one table-row's spec (the plan spec minus
    the slot axis), consumed by the fused _admit without resharding."""
    cfg, model, params = dit
    eng = _sharded(model, params, "fastcache", topo=(1, 1))
    assert eng._plan_row_sh.spec == P(*eng._plan_sh["ts"].spec[1:])
    req = DiffusionRequest(rid=0, label=1, seed=5, num_steps=3,
                           guidance_scale=2.0)
    plan = eng.resolve_plan(req)
    ts_row, prev_row = plan.rows(eng.max_steps, eng.num_train_steps)
    staged = eng._staged_plan(ts_row, prev_row)
    assert all(s.sharding == eng._plan_row_sh for s in staged)
    eng.add_request(req)
    assert eng.plan["ts"].sharding.spec == eng._plan_sh["ts"].spec


@multi_device
def test_sharded_bench_weights_schedule_parity():
    """Real (non-adaLN-zero) weights: XLA:CPU gemms are batch-shape
    sensitive — the same row in a 2-row and an 8-row matmul can differ in
    the last bits, so sharded latents drift from the single-device run at
    fp-reassociation scale (the topology benchmark reports the honest
    max-abs-diff).  The *runtime* contract still holds exactly: identical
    admission/finish scheduling, step counts and per-request latencies,
    with latents equal to tolerance."""
    from benchmarks.common import build_dit
    cfg, model, params = build_dit("dit-b2")
    res = {}
    for topo in (None, (4, 1)):
        runner = CachedDiT(model, FastCacheConfig(), policy="fastcache")
        eng = (DiffusionServingEngine(runner, params, max_slots=4,
                                      num_steps=STEPS) if topo is None else
               ShardedDiffusionEngine(runner, params, max_slots=4,
                                      num_steps=STEPS,
                                      mesh=make_serving_mesh(*topo)))
        done = eng.run(_staggered_trace())
        res[topo] = ({r.rid: (r.admit_step, r.finish_step, r.latency_steps)
                      for r in done},
                     {r.rid: np.asarray(r.latents) for r in done},
                     (eng.clock, eng.model_steps))
    sched_a, lat_a, steps_a = res[None]
    sched_b, lat_b, steps_b = res[(4, 1)]
    assert sched_a == sched_b
    assert steps_a == steps_b
    for rid in lat_a:
        np.testing.assert_allclose(lat_a[rid], lat_b[rid], atol=0.5,
                                   err_msg=f"rid={rid}")


@multi_device
def test_sharded_lockstep_mode(dit):
    cfg, model, params = dit
    eng = _sharded(model, params, "fastcache", topo=(4, 1))
    done = eng.run(_staggered_trace(), lockstep=True)
    assert len(done) == 5 and all(r.done for r in done)


# ---------------------------------------------------------------------------
# Satellite: reproducible Poisson traces (explicit seed or jax.random key)
# ---------------------------------------------------------------------------

def test_poisson_trace_requires_explicit_seed_or_key():
    with pytest.raises(TypeError):
        poisson_trace(4, 0.5, num_classes=10)
    with pytest.raises(TypeError):
        poisson_trace(4, 0.5, seed=1, key=jax.random.PRNGKey(1),
                      num_classes=10)
    # num_classes has no default either: it must come from the model config
    with pytest.raises(TypeError):
        poisson_trace(4, 0.5, seed=1)


def test_poisson_trace_key_is_deterministic():
    a = poisson_trace(16, 0.5, key=jax.random.PRNGKey(42), num_classes=10)
    b = poisson_trace(16, 0.5, key=jax.random.PRNGKey(42), num_classes=10)
    assert [(r.arrival_step, r.label, r.seed) for r in a] == \
        [(r.arrival_step, r.label, r.seed) for r in b]
    c = poisson_trace(16, 0.5, key=jax.random.PRNGKey(43), num_classes=10)
    assert [r.arrival_step for r in a] != [r.arrival_step for r in c] or \
        [r.label for r in a] != [r.label for r in c]
