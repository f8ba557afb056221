#!/usr/bin/env python3
"""Chip smoke: serve DiT-XL/2 through the FastCache serving path on a TPU
and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # four chips: the multi-chip paths only

The model is DiT-XL/2 at full width (28 layers, d=1152, 18 heads) in bf16,
with 256x256 images as 32x32x4 latents, classifier-free guidance 4.0 and
seeded weights made non-degenerate and well-conditioned by
``repro.models.dit.unzero_params``.
A Poisson trace of 8 requests with 20- and 50-step budgets is served through
``DiffusionServingEngine`` with 4 slots, as ``repro.launch.serve_diffusion``
serves it.

Phases on one chip:

  kernels   the compiled Pallas kernels of the serving path against their
            ``kernels/ref.py`` twins at serving widths;
  serve     the trace under ``nocache``, ``fastcache`` and ``fastcache`` with
            token merging: the fused kernels are selected and compiled into
            the serve step, every served request matches a solo ``sample()``
            replay, a second run of the trace repeats the first bit for bit
            with no compile inside its window;
  fused     fastcache with the fused gate against ``use_fused_gate=False``;
  float32   the nocache latents against a float32 forward at the highest
            matmul precision.

With ``--four-chips``: ``ShardedDiffusionEngine`` on (data, model) meshes
(4, 1) and (2, 2) against the single-device engine, and a ``ReplicaRouter``
over four one-device replicas, each on its own chip.

Every tolerance is stated next to its check (``TOL``).  The script exits
non-zero, printing no result, when the first device is not a TPU, when the
repository's sources are not next to it, or when any check or phase fails.
Otherwise the last line of stdout is one JSON object with the device as JAX
reports it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "dit-xl2"
SLOTS = 4
REQUESTS = 8
STEPS_MIX = (20, 50)
GUIDANCE = 4.0
RATE = 0.5
TRACE_SEED = 3
MERGE = dict(merge_enabled=True, merge_ratio=0.5, merge_window=16)

# relative L2 distances ||got - ref|| / ||ref|| unless stated otherwise
TOL = {
    # bf16 kernel outputs against the f32 reference at the highest matmul
    # precision: a few bf16 roundings (2^-8 relative each)
    "kernel_bf16_out": 1e-2,
    # f32 sums of ~1e5 squares in another order
    "kernel_f32_sums": 1e-4,
    # knn density exp(-mean dist / d): f32 distances with cancellation
    "kernel_knn_max_abs": 1e-4,
    # one request served in another batch: against its solo replay, or on
    # a mesh against the single-device engine.  On the TPU another batch
    # shape moves bf16 results by a rounding step: carried along 50 steps
    # that is 2.3e-2 without a cache (TPU v5e).  With fastcache and
    # merging the same noise also flips discrete gate and merge decisions,
    # each swapping a block for its linear approximation or regrouping a
    # window: 7.0e-2 (also on a (4, 1) mesh) and 1.8e-1 on the same chip.
    # Unrelated latents sit at sqrt(2).
    "other_batch": 5e-2,
    "other_batch_gated": 3e-1,
    # teacher-forced fused vs reference gate: both gates see the same
    # inputs each step; share of (row, step) pairs with equal skipped-block
    # counts, and the step's output latents
    "fused_gate_agreement": 0.95,
    "fused_step_latents": 2e-2,
    # free-running fused vs reference trajectories after all steps
    "fused_final_latents": 5e-2,
    # bf16 vs a float32 forward at the highest matmul precision: the guided
    # eps of one step from the same latents (the bf16 blocks' rounding,
    # amplified by the guidance blend: 1.2e-2 after 8 blocks at this width
    # on the CPU; a wrong forward is off by O(1)), and the free-running
    # trajectories' end points (two unrelated latents sit at sqrt(2))
    "float32_step_eps": 1e-1,
    "float32_final": 0.5,
}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class Checks:
    """Collects named checks; any failure fails the run."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"check {name}: {'ok' if ok else 'FAILED'} {detail}".rstrip())
        if not ok:
            self.failed.append(name)


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits through
    ``jax.monitoring``."""

    def __init__(self, jax):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits


def rel_l2(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def finite(*arrays) -> bool:
    import numpy as np
    return all(bool(np.isfinite(np.asarray(a, np.float64)).all())
               for a in arrays)


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_kernels(check, *, rows: int, tokens: int, d: int, capacity: float,
                  window: int, ratio: float, seed: int) -> None:
    """Compiled kernels against their ``ref.py`` twins at serving shapes:
    ``rows`` state rows (slots x CFG pair) of ``tokens`` tokens, motion
    length ``capacity * tokens``, ``window``-token merge windows."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import statcache
    from repro.kernels import fused_gate as fg
    from repro.kernels import knn_density as kd
    from repro.kernels import ref
    from repro.kernels import token_merge as tm

    bf16, f32 = jnp.bfloat16, jnp.float32
    c = int(round(capacity * tokens))
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)

    # fused gate: per-row statistics placed at half and twice the threshold
    # so the decisions are unambiguous; one row is ineligible
    x = jax.random.normal(keys[0], (rows, c, d), f32)
    scale = jnp.linspace(0.05, 1.0, rows)[:, None, None]
    prev_in = (x + scale * jax.random.normal(keys[1], x.shape)).astype(bf16)
    x = x.astype(bf16)
    prev_out = jax.random.normal(keys[2], (rows, c, d), bf16)
    w = (jnp.eye(d) + jax.random.normal(keys[3], (d, d)) / d).astype(f32)
    b = 0.1 * jax.random.normal(keys[4], (d,), f32)
    nd = c * d
    threshold = statcache.make_threshold(0.05, nd)
    diff = np.sum(np.square(np.asarray(x, np.float64)
                            - np.asarray(prev_in, np.float64)), axis=(1, 2))
    target = np.where(np.arange(rows) % 2 == 0, 0.5, 2.0) * threshold
    sigma2 = jnp.asarray(diff / (nd * target), f32)
    eligible = jnp.asarray(np.arange(rows) != 1)
    args = (x, prev_in, prev_out, w, b, sigma2, eligible)
    kw = dict(threshold=threshold, gamma=0.5, use_blend=True)
    out, gate, dsq, psq = fg.fused_gate(*args, interpret=False, **kw)
    with jax.default_matmul_precision("highest"):
        r_out, r_gate, r_dsq, r_psq = jax.jit(
            lambda *a: ref.fused_gate(*a, **kw))(*args)
    want = (target < threshold) & np.asarray(eligible)
    check("kernels.fused_gate.gate",
          np.array_equal(np.asarray(gate), np.asarray(r_gate))
          and np.array_equal(np.asarray(gate), want),
          f"gated rows {np.flatnonzero(np.asarray(gate)).tolist()}")
    e_sum = max(rel_l2(dsq, r_dsq), rel_l2(psq, r_psq))
    check("kernels.fused_gate.sums", e_sum <= TOL["kernel_f32_sums"],
          f"rel_l2={e_sum:.3e} tol={TOL['kernel_f32_sums']}")
    e_out = rel_l2(out, r_out)
    check("kernels.fused_gate.out", e_out <= TOL["kernel_bf16_out"]
          and finite(out), f"rel_l2={e_out:.3e} tol={TOL['kernel_bf16_out']}")

    # token merging: (windows, window, d) bf16 tokens, per-window
    # normalized positive importance
    n_win = rows * tokens // window
    m = max(1, int(np.ceil(ratio * window)))
    h = jax.random.normal(keys[5], (n_win, window, d), bf16)
    s = jax.random.uniform(keys[6], (n_win, window), f32, 0.1, 1.0)
    s = s / jnp.max(s, axis=-1, keepdims=True)
    rho = kd.knn_density(h, k=5, interpret=False)
    with jax.default_matmul_precision("highest"):
        r_rho = jax.jit(lambda h: ref.knn_density(h, 5))(h)
        r_merged, r_assign, r_centers = jax.jit(
            lambda h, s: ref.merge_assign(h, s, m))(h, s)
    e_rho = float(np.max(np.abs(np.asarray(rho) - np.asarray(r_rho))))
    check("kernels.knn_density", e_rho <= TOL["kernel_knn_max_abs"],
          f"max_abs={e_rho:.3e} tol={TOL['kernel_knn_max_abs']}")
    merged, assign, centers = tm.merge_assign(h, s, m=m, interpret=False)
    check("kernels.merge_assign.ids",
          np.array_equal(np.asarray(assign), np.asarray(r_assign))
          and np.array_equal(np.asarray(centers), np.asarray(r_centers)))
    e_m = rel_l2(merged, r_merged)
    check("kernels.merge_assign.merged", e_m <= TOL["kernel_bf16_out"],
          f"rel_l2={e_m:.3e} tol={TOL['kernel_bf16_out']}")
    un = tm.unmerge_scatter(r_merged, r_assign, interpret=False)
    r_un = ref.unmerge_scatter(r_merged, r_assign)
    check("kernels.unmerge_scatter", np.array_equal(np.asarray(un),
                                                    np.asarray(r_un)),
          "exact gather")


def make_trace(num_classes: int):
    from repro.serving import poisson_trace
    return poisson_trace(REQUESTS, RATE, seed=TRACE_SEED,
                         num_classes=num_classes, steps_mix=STEPS_MIX)


def serve_setup(check, counter, dev, name: str, model, params, fc,
                policy: str, *, expect_kernels: bool):
    """Serve the trace twice (cold, then steady) under one setup; replay
    every request solo.  Returns the steady run's finished requests."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import CachedDiT
    from repro.diffusion import sample
    from repro.serving import DiffusionServingEngine

    runner = CachedDiT(model, fc, policy=policy)
    if expect_kernels:
        check(f"{name}.use_fused", bool(runner.use_fused),
              f"use_fused={runner.use_fused}")
    max_steps = max(STEPS_MIX)
    eng = DiffusionServingEngine(runner, params, max_slots=SLOTS,
                                 num_steps=max_steps,
                                 guidance_scale=GUIDANCE,
                                 max_steps=max_steps)
    n0, s0, h0 = counter.snapshot()
    t0 = time.perf_counter()
    cold = eng.run(make_trace(model.cfg.dit.num_classes))
    jax.block_until_ready(eng.x)
    cold_s = time.perf_counter() - t0
    n1, s1, h1 = counter.snapshot()

    eng.reset_clock()
    t0 = time.perf_counter()
    done = eng.run(make_trace(model.cfg.dit.num_classes))
    jax.block_until_ready(eng.x)
    wall = time.perf_counter() - t0
    n2, _, _ = counter.snapshot()
    stats = eng.cache_stats()
    step_ms = 1e3 * wall / max(eng.model_steps, 1)
    log(f"serve {name}: compile_s={s1 - s0:.1f} ({n1 - n0} compiles, "
        f"{h1 - h0} persistent-cache hits, cold run {cold_s:.1f}s) "
        f"serve_step_ms={step_ms:.2f} (steady run "
        f"{wall:.3f}s / {eng.model_steps} serve steps, up to "
        f"block_until_ready, {n2 - n1} compiles inside) "
        f"cache_ratio={stats['block_cache_ratio']:.4f} "
        f"peak_bytes_in_use={peak_bytes(dev)}")
    check(f"{name}.steady_compiles", n2 == n1, f"{n2 - n1} compiles")
    budgets = sorted({r.num_steps for r in done})
    check(f"{name}.served", len(done) == REQUESTS
          and budgets == sorted(STEPS_MIX),
          f"{len(done)} requests, budgets {budgets}")
    by_rid = {r.rid: r.latents for r in cold}
    check(f"{name}.rerun_bitwise",
          all(np.array_equal(by_rid[r.rid], r.latents) for r in done))
    check(f"{name}.finite", finite(*[r.latents for r in done]))

    if expect_kernels:
        S = eng.S
        hlo = eng._step.lower(
            eng.params, eng.state, eng.x, eng.plan,
            jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), bool), eng.acc, eng.slot_acc, eng.metrics,
            jnp.asarray(False)).compile().as_text()
        n = hlo.count("tpu_custom_call")
        check(f"{name}.hlo_tpu_custom_call", n > 0, f"{n} occurrences")

    tol = TOL["other_batch" if policy == "nocache" else "other_batch_gated"]
    worst, bitwise, skip_gap = 0.0, 0, 0.0
    for r in done:
        x, st = sample(runner, params, jax.random.PRNGKey(0), batch=1,
                       labels=jnp.array([r.label]), num_steps=r.num_steps,
                       guidance_scale=r.guidance_scale,
                       x_init=eng.request_noise(r)[None])
        x = np.asarray(x[0])
        worst = max(worst, rel_l2(r.latents, x))
        bitwise += int(np.array_equal(x, r.latents))
        # skipped blocks of the request's CFG pair, served vs solo, as a
        # share of the pair's block evaluations
        solo_skips = float(np.sum(st["stats"].get("blocks_skipped", 0.0)))
        skip_gap = max(skip_gap, abs(r.cache.get("blocks_skipped", 0.0)
                                     - solo_skips)
                       / (2 * runner.L * r.num_steps))
    check(f"{name}.solo_replay", worst <= tol,
          f"max rel_l2={worst:.3e} tol={tol} ({bitwise}/{len(done)} "
          f"bitwise, skipped-block share differs by at most "
          f"{skip_gap:.4f})")
    return eng, done


def phase_fused(check, model, params, *, steps: int, rows: int,
                seed: int) -> None:
    """Fastcache with the fused gate against ``use_fused_gate=False``:
    teacher-forced (the reference gate steps from the fused run's state
    each step) for decision agreement, and free-running for latents."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import FastCacheConfig
    from repro.core import CachedDiT
    from repro.diffusion import schedule as sch
    from repro.diffusion.sampler import denoise_step

    fused = CachedDiT(model, FastCacheConfig(), policy="fastcache")
    plain = CachedDiT(model, FastCacheConfig(use_fused_gate=False),
                      policy="fastcache")
    check("fused.use_fused", fused.use_fused and not plain.use_fused)
    dit = model.cfg.dit
    x0 = jax.random.normal(jax.random.PRNGKey(seed),
                           (rows, dit.image_size, dit.image_size,
                            dit.in_channels), jnp.float32)
    labels = jnp.arange(rows, dtype=jnp.int32) % dit.num_classes
    sched = sch.linear_schedule(1000)
    ts = sch.ddim_timesteps(1000, steps)
    ts_prev = jnp.concatenate([ts[1:], jnp.array([-1], jnp.int32)])
    step_f = jax.jit(functools.partial(denoise_step, fused,
                                       guidance_scale=GUIDANCE))
    step_p = jax.jit(functools.partial(denoise_step, plain,
                                       guidance_scale=GUIDANCE))
    sf, sp = fused.init_state(2 * rows), plain.init_state(2 * rows)
    xf = xp = x0
    agree = total = 0
    worst_step = 0.0
    for i in range(steps):
        t = jnp.full((rows,), ts[i])
        tp = jnp.full((rows,), ts_prev[i])
        xf_new, sf_new = step_f(params, sched, sf, xf, t, tp, labels)
        xt, st = step_p(params, sched, sf, xf, t, tp, labels)
        before = np.asarray(sf["stats"]["blocks_skipped"])
        skip_f = np.asarray(sf_new["stats"]["blocks_skipped"]) - before
        skip_t = np.asarray(st["stats"]["blocks_skipped"]) - before
        agree += int(np.sum(skip_f == skip_t))
        total += skip_f.size
        worst_step = max(worst_step, rel_l2(xt, xf_new))
        xp, sp = step_p(params, sched, sp, xp, t, tp, labels)
        xf, sf = xf_new, sf_new
    share = agree / total
    ratio = {k: float(np.sum(s["stats"]["blocks_skipped"])
                      / max(np.sum(s["stats"]["blocks_skipped"])
                            + np.sum(s["stats"]["blocks_computed"]), 1.0))
             for k, s in (("fused", sf), ("reference", sp))}
    log(f"fused: {rows} requests x {steps} steps, cache_ratio fused="
        f"{ratio['fused']:.4f} reference={ratio['reference']:.4f}")
    check("fused.gate_agreement", share >= TOL["fused_gate_agreement"],
          f"{agree}/{total} (row, step) pairs = {share:.4f} "
          f"tol>={TOL['fused_gate_agreement']}")
    check("fused.step_latents", worst_step <= TOL["fused_step_latents"],
          f"max rel_l2={worst_step:.3e} tol={TOL['fused_step_latents']}")
    e = rel_l2(xf, xp)
    check("fused.final_latents", e <= TOL["fused_final_latents"]
          and finite(xf, xp),
          f"rel_l2={e:.3e} tol={TOL['fused_final_latents']}")


def phase_float32(check, model, params, served) -> None:
    """The nocache serving path against a float32 forward at the highest
    matmul precision, for one served request of each step budget.  Each
    step, both models evaluate the same bf16 latents (teacher forcing), so
    the guided eps compares forward against forward; the bf16 trajectory
    must also end on the served latents, and the free-running float32
    trajectory must end near them."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import FastCacheConfig
    from repro.core import CachedDiT
    from repro.diffusion import schedule as sch
    from repro.diffusion.sampler import denoise_step
    from repro.models import build_model

    model32 = build_model(model.cfg.replace(dtype="float32"))
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    runner16 = CachedDiT(model, FastCacheConfig(), policy="nocache")
    runner32 = CachedDiT(model32, FastCacheConfig(), policy="nocache")
    sched = sch.linear_schedule(1000)
    eng, done = served
    picked = {}
    for r in done:
        picked.setdefault(r.num_steps, r)
    for n, r in sorted(picked.items()):
        step16 = jax.jit(functools.partial(
            denoise_step, runner16, guidance_scale=r.guidance_scale,
            return_eps=True))
        step32 = jax.jit(functools.partial(
            denoise_step, runner32, guidance_scale=r.guidance_scale,
            return_eps=True))
        ts = sch.ddim_timesteps(1000, n)
        ts_prev = jnp.concatenate([ts[1:], jnp.array([-1], jnp.int32)])
        labels = jnp.array([r.label])
        x16 = x32 = eng.request_noise(r)[None]
        s16, s32 = runner16.init_state(2), runner32.init_state(2)
        worst = 0.0
        for i in range(n):
            t, tp = ts[i:i + 1], ts_prev[i:i + 1]
            nxt, s16, eps16 = step16(params, sched, s16, x16, t, tp, labels)
            with jax.default_matmul_precision("highest"):
                _, _, eps32 = step32(params32, sched, s32, x16, t, tp,
                                     labels)
                x32, s32, _ = step32(params32, sched, s32, x32, t, tp,
                                     labels)
            worst = max(worst, rel_l2(eps16, eps32))
            x16 = nxt
        check(f"float32.{n}_steps.eps", worst <= TOL["float32_step_eps"]
              and finite(x16, x32),
              f"rid={r.rid} max per-step rel_l2={worst:.3e} "
              f"tol={TOL['float32_step_eps']}")
        e16 = rel_l2(r.latents, np.asarray(x16[0]))
        check(f"float32.{n}_steps.bf16_replay",
              e16 <= TOL["other_batch"],
              f"rel_l2={e16:.3e} tol={TOL['other_batch']}")
        e32 = rel_l2(r.latents, np.asarray(x32[0]))
        check(f"float32.{n}_steps.final", e32 <= TOL["float32_final"],
              f"rel_l2={e32:.3e} tol={TOL['float32_final']}")


def run_one_chip(check, counter, dev, *, seed: int) -> None:
    import jax
    from repro.configs import get_config
    from repro.configs.base import FastCacheConfig
    from repro.models import build_model
    from repro.models.dit import unzero_params

    cfg = get_config(ARCH)
    log(f"model {cfg.name}: {cfg.num_layers} layers, d={cfg.d_model}, "
        f"{cfg.num_heads} heads, {cfg.dtype}, "
        f"{cfg.dit.image_size}x{cfg.dit.image_size}x{cfg.dit.in_channels} "
        f"latents, guidance {GUIDANCE}")
    model = build_model(cfg)
    params = unzero_params(model.init(jax.random.PRNGKey(seed)),
                           jax.random.PRNGKey(seed + 1),
                           rescale_attention=True)
    tokens = model.num_tokens
    phases = [
        ("kernels", lambda: phase_kernels(
            check, rows=2 * SLOTS, tokens=tokens, d=cfg.d_model,
            capacity=FastCacheConfig().motion_capacity,
            window=MERGE["merge_window"], ratio=MERGE["merge_ratio"],
            seed=seed)),
    ]
    served = {}
    for name, fc, policy, kern in (
            ("nocache", FastCacheConfig(), "nocache", False),
            ("fastcache", FastCacheConfig(), "fastcache", True),
            ("fastcache_merge", FastCacheConfig(**MERGE), "fastcache",
             True)):
        phases.append((f"serve.{name}", lambda name=name, fc=fc,
                       policy=policy, kern=kern: served.__setitem__(
                           name, serve_setup(check, counter, dev, name,
                                             model, params, fc, policy,
                                             expect_kernels=kern))))
    phases += [
        ("fused", lambda: phase_fused(check, model, params,
                                      steps=min(STEPS_MIX), rows=SLOTS,
                                      seed=seed)),
        ("float32", lambda: phase_float32(check, model, params,
                                          served["nocache"])),
    ]
    run_phases(check, phases)


def run_four_chips(check, *, seed: int) -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import FastCacheConfig
    from repro.core import CachedDiT
    from repro.launch.mesh import make_serving_mesh
    from repro.models import build_model
    from repro.models.dit import unzero_params
    from repro.serving import (DiffusionServingEngine, ReplicaRouter,
                               ShardedDiffusionEngine, SLOScheduler)

    devs = jax.devices()[:4]
    cfg = get_config(ARCH)
    model = build_model(cfg)
    params = unzero_params(model.init(jax.random.PRNGKey(seed)),
                           jax.random.PRNGKey(seed + 1),
                           rescale_attention=True)
    max_steps = max(STEPS_MIX)
    kw = dict(max_slots=SLOTS, num_steps=max_steps, guidance_scale=GUIDANCE,
              max_steps=max_steps)

    def trace():
        return make_trace(cfg.dit.num_classes)

    def single(policy):
        eng = DiffusionServingEngine(CachedDiT(model, FastCacheConfig(),
                                               policy=policy), params, **kw)
        return {r.rid: r.latents for r in eng.run(trace())}, policy

    def placed_on(eng):
        leaves = jax.tree.leaves((eng.params, eng.state, eng.x, eng.plan))
        return set().union(*(leaf.sharding.device_set for leaf in leaves))

    def compare(name, done, single_run, t0, eng_steps):
        ref, policy = single_run
        tol = TOL["other_batch" if policy == "nocache"
                  else "other_batch_gated"]
        worst = max(rel_l2(r.latents, ref[r.rid]) for r in done)
        log(f"{name}: {len(done)} requests, {eng_steps} engine steps in "
            f"{time.perf_counter() - t0:.1f}s")
        check(f"{name}.latents", len(done) == len(ref) and worst <= tol
              and finite(*[r.latents for r in done]),
              f"max rel_l2={worst:.3e} vs single device tol={tol}")

    refs = {}

    def sharded(data, tp):
        if "fastcache" not in refs:
            refs["fastcache"] = single("fastcache")
        name = f"sharded.{data}x{tp}"
        eng = ShardedDiffusionEngine(
            CachedDiT(model, FastCacheConfig(), policy="fastcache"),
            params, mesh=make_serving_mesh(data, tp, devices=devs), **kw)
        if tp > 1:
            step, leaf, used = eng.numerics_drift
            log(f"{name}: numerics self-check passed, furthest leaf {leaf} "
                f"at step {step} used {used:.3f} of its tolerance")
        check(f"{name}.placement", placed_on(eng) == set(devs),
              f"arrays on {sorted(d.id for d in placed_on(eng))}")
        t0 = time.perf_counter()
        done = eng.run(trace())
        compare(name, done, refs["fastcache"], t0, eng.clock)

    def replica_router():
        ref = single("nocache")
        scheds = [SLOScheduler(ShardedDiffusionEngine(
            CachedDiT(model, FastCacheConfig(), policy="nocache"), params,
            mesh=make_serving_mesh(1, 1, devices=[d]), **kw),
            sched_policy="fifo") for d in devs]
        rt = ReplicaRouter(scheds)
        t0 = time.perf_counter()
        done = rt.run(trace())
        served = np.bincount(list(rt.dispatched.values()),
                             minlength=len(devs)).tolist()
        for i, (s, d) in enumerate(zip(scheds, devs)):
            check(f"router.replica{i}.placement",
                  placed_on(s.engine) == {d},
                  f"arrays on {sorted(x.id for x in placed_on(s.engine))}")
        check("router.all_replicas_served", min(served) > 0,
              f"requests per replica {served}")
        compare("router", done, ref, t0, max(s.engine.clock for s in scheds))

    # (2, 2), not (1, 4): at model=4 the 18 heads do not divide and fall
    # back to replicated weights
    phases = [(f"sharded.{data}x{tp}", lambda data=data, tp=tp:
               sharded(data, tp)) for data, tp in ((4, 1), (2, 2))]
    phases.append(("router", replica_router))
    run_phases(check, phases)


def run_phases(check, phases) -> None:
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            check(f"phase.{name}", False, "raised")
        else:
            log(f"phase {name} done in {time.perf_counter() - t0:.1f}s")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip phases (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, noise and kernel inputs")
    args = ap.parse_args()

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no src/repro next to {os.path.basename(__file__)}: run it "
             f"from a checkout of the repository")
    sys.path.insert(0, src)
    # the TPU runtime otherwise writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    try:
        devs = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no devices: {e}")
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform!r} "
             f"({dev.device_kind})")
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        fail(f"--four-chips needs 4 chips, JAX sees {len(devs)}")
    log(f"device {dev.device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")

    counter = CompileCounter(jax)
    check = Checks()
    t0 = time.perf_counter()
    if args.four_chips:
        run_four_chips(check, seed=args.seed)
    else:
        run_one_chip(check, counter, dev, seed=args.seed)
    log(f"total {time.perf_counter() - t0:.1f}s, {counter.compiles} "
        f"compiles ({counter.compile_s:.1f}s), {counter.cache_hits} "
        f"persistent-cache hits, peak_bytes_in_use={peak_bytes(dev)}")
    if check.failed:
        fail(f"{len(check.failed)} checks failed: {check.failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
