"""Overload benchmark: goodput vs p99 latency per cache-ratio shedding level.

    PYTHONPATH=src python -m benchmarks.serving_overload [--json out.json]

A bursty arrival trace (rate-modulated Poisson: calm -> burst -> calm, via
``piecewise_rate``) with mixed priority classes and per-request deadlines is
served through the SLO control plane (``SLOScheduler``: EDF admission,
deadline-aware rejection, priority preemption) once per **shedding level**.
Each level of the ladder is a ``ShedLevel`` pinned for the whole run
(single-level ``DegradationController``), combining the two degradation
knobs:

- ``steps_scale`` — shrink the DDIM step budget of shed-eligible classes
  (``min_priority`` and above) at admission.  Zero-recompile: the plan
  tables already support heterogeneous budgets.
- ``alpha`` — the chi^2 significance of the cache gate, applied at ENGINE
  CONSTRUCTION (thresholds are trace-time constants; see
  ``slo/controller.py``).  Smaller alpha -> higher skip threshold -> more
  cache reuse -> faster steps but larger approximation error.

Per level the benchmark reports **goodput** (fraction of offered requests
finishing within their deadline — deadlines live on the engine-step clock,
so this is deterministic and wall-noise-free), step-clock latency
p50/p99, queue wait, rejections/preemptions, and the **audit-measured
quality cost**: a second run with ``audit_fraction=1.0`` shadow-computes
the uncached forward on every step; the headline ``quality_cost`` is the
mean cached-vs-true eps error per gated audited slot-step from the exact
per-request error budgets (the PR 8 audit plane pricing each shedding
level in quality), with the histogram quantiles alongside.  The
acceptance story is the committed
ladder showing monotonically increasing goodput AND audit error across
levels — shedding buys deadline hits with quality, and the audit plane
shows exactly how much.

Also runnable through benchmarks/run.py (suite ``serving_overload``);
``--bench-out BENCH_serving.json`` appends one trajectory entry (suite
tag ``serving_overload``) next to the ``serving`` entries, gated by
``benchmarks/bench_check.py``.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.common import build_dit
from benchmarks.serving_diffusion import _fresh_trace, append_entry
from repro.configs.base import FastCacheConfig
from repro.core import CachedDiT
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import MetricsCollector
from repro.serving import (DegradationController, DiffusionRequest,
                           DiffusionServingEngine, ShedLevel, SLOScheduler,
                           piecewise_rate, poisson_trace,
                           summarize_by_class)

# The committed ladder: each level sheds harder on every axis, so goodput
# and quality cost move together monotonically.  ``steps_scale`` drives
# the step-clock goodput; ``capacity_scale`` + ``alpha`` drive the
# quality cost (``capacity_scale`` is the axis that bites at reduced CPU
# scale — it routes more tokens through the STR static bypass every
# step, while the chi^2 stat sits far above any alpha-reachable
# threshold on a randomly-initialized reduced model; alpha still drops
# per rung so the ladder is production-shaped).  alpha=None on the
# nominal level means "the FastCacheConfig default" (0.05).  The scales
# balance two opposing error effects — smaller capacity raises the
# per-step approximation error, while a shorter budget samples fewer
# high-error late steps — so each rung's measured quality cost stays
# strictly above the previous one's (tuned on the default trace; see the
# sweep rationale in the PR adding this file).
DEFAULT_LADDER: Tuple[ShedLevel, ...] = (
    ShedLevel("nominal", steps_scale=1.0, alpha=None, capacity_scale=1.0),
    ShedLevel("shed-1", steps_scale=0.875, alpha=1e-3,
              capacity_scale=0.375),
    ShedLevel("shed-2", steps_scale=0.75, alpha=1e-8,
              capacity_scale=0.0625),
)


def overload_trace(*, requests: int, num_classes: int, seed: int,
                   base_rate: float, burst_rate: float, burst_start: int,
                   burst_len: int, priority_mix: Sequence[int],
                   deadline_slack: Sequence[int]) -> List[DiffusionRequest]:
    """Calm -> burst -> calm arrivals with priority classes and
    deadlines.  The burst is what builds the queue the control plane
    sheds against; the calm tail lets every admitted request drain so
    goodput compares complete runs."""
    rate_fn = piecewise_rate([(burst_start, base_rate),
                              (burst_start + burst_len, burst_rate),
                              (10 ** 9, base_rate)])
    return poisson_trace(requests, base_rate, seed=seed,
                         num_classes=num_classes, rate_fn=rate_fn,
                         priority_mix=tuple(priority_mix),
                         deadline_slack_mix=tuple(deadline_slack))


def serve_level(model, params, trace: List[DiffusionRequest],
                level: ShedLevel, *, policy: str = "fastcache",
                slots: int, steps: int, guidance: float,
                audit_fraction: float = 0.0,
                collector: Optional[MetricsCollector] = None,
                repeats: int = 1
                ) -> Tuple[Dict, List[DiffusionRequest], SLOScheduler]:
    """One SLO-controlled run of ``trace`` pinned at ``level``.  Returns
    (result row, finished requests, scheduler) — the scheduler exposes
    ``.rejected`` for the admission-loss accounting.

    Every scheduling outcome (goodput, rejections, preemptions,
    latencies) lives on the deterministic engine-step clock, so repeats
    reproduce it bitwise; only the wall clock varies.  ``repeats`` runs
    the trace that many times on the warm engine and keeps the best-wall
    run for the ``model_step_ms`` measurement, the same noise-floor
    idiom as the serving trajectory's best-of-N."""
    base = FastCacheConfig()
    fc = FastCacheConfig(
        alpha=level.alpha if level.alpha is not None else base.alpha,
        motion_capacity=base.motion_capacity * level.capacity_scale)
    runner = CachedDiT(model, fc, policy=policy)
    engine = DiffusionServingEngine(runner, params, max_slots=slots,
                                    num_steps=steps,
                                    guidance_scale=guidance,
                                    collector=collector,
                                    audit_fraction=audit_fraction)
    # warm the jitted step so wall time excludes compilation, then rewind
    # the clock so the trace's absolute arrival steps (and deadlines,
    # which live on the same clock) line up
    warm = _fresh_trace(trace[:1])
    warm[0].arrival_step = 0
    warm[0].deadline_step = None
    warm[0].priority = 0
    engine.run(warm)
    best = None
    for _ in range(max(1, repeats)):
        engine.reset_clock()
        controller = DegradationController(levels=(level,),
                                           collector=collector)
        sched = SLOScheduler(engine, sched_policy="edf",
                             controller=controller, collector=collector)
        reqs = _fresh_trace(trace)
        t0 = time.perf_counter()
        done = sched.run(reqs)
        wall = time.perf_counter() - t0
        if best is None or wall < best[0]:
            best = (wall, done, sched)
    wall, done, sched = best
    offered = len(trace)
    met = sum(1 for r in done
              if r.deadline_step is None or r.finish_step <= r.deadline_step)
    lats = np.array([r.latency_steps for r in done] or [-1.0], np.float64)
    waits = np.array([r.queue_wait_steps for r in done] or [-1.0],
                     np.float64)
    row = {
        "level": level.name,
        "policy": f"{policy}@{level.name}",
        "steps_scale": level.steps_scale,
        "alpha": fc.alpha,
        "capacity_scale": level.capacity_scale,
        "min_priority": level.min_priority,
        "offered": offered,
        "finished": len(done),
        "rejected": len(sched.rejected),
        "deadline_met": met,
        "goodput": met / offered if offered else 0.0,
        "preemptions": sum(r.preemptions for r in done),
        "latency_steps_p50": float(np.percentile(lats, 50)),
        "latency_steps_p99": float(np.percentile(lats, 99)),
        "queue_wait_p50": float(np.percentile(waits, 50)),
        "queue_wait_p95": float(np.percentile(waits, 95)),
        "engine_steps": engine.clock,
        "model_steps": engine.model_steps,
        "wall_s": wall,
        "model_step_ms": wall / max(1, engine.model_steps) * 1e3,
        "steps_per_s": engine.model_steps / wall if wall else 0.0,
        "cache_ratio": engine.cache_stats()["block_cache_ratio"],
    }
    return row, done, sched


def _monotone(xs: Sequence[float], *, strict: bool = False) -> bool:
    eps = 1e-12
    return all(b > a if strict else b >= a - eps
               for a, b in zip(xs, xs[1:]))


def _levels_config(levels: Sequence[ShedLevel]) -> List[Dict]:
    return [{"name": lv.name, "steps_scale": lv.steps_scale,
             "alpha": lv.alpha, "capacity_scale": lv.capacity_scale,
             "min_priority": lv.min_priority}
            for lv in levels]


def _levels_from_config(spec: Sequence[Dict]) -> Tuple[ShedLevel, ...]:
    return tuple(ShedLevel(d["name"], steps_scale=d["steps_scale"],
                           alpha=d.get("alpha"),
                           capacity_scale=d.get("capacity_scale", 1.0),
                           min_priority=d.get("min_priority", 1))
                 for d in spec)


def benchmark(*, dit: str = "dit-b2", policy: str = "fastcache",
              requests: int = 24, slots: int = 2, steps: int = 8,
              guidance: float = 4.0, seed: int = 0,
              base_rate: float = 0.1, burst_rate: float = 1.5,
              burst_start: int = 2, burst_len: int = 12,
              priority_mix: Sequence[int] = (0, 1, 1, 2),
              deadline_slack: Sequence[int] = (12, 20, 32),
              levels: Sequence[ShedLevel] = DEFAULT_LADDER,
              repeats: int = 2) -> Dict:
    """Serve the same bursty trace once per shedding level: a perf run
    (metrics on, audit off — goodput / latency / step time, best wall of
    ``repeats``) plus a fully-audited quality run (``audit_fraction=1.0``
    — the realized cached-vs-true error this level pays).  Goodput and
    latency live on the deterministic engine-step clock, so the
    level-to-level curves are reproducible; only ``model_step_ms`` is
    wall-derived."""
    cfg, model, params = build_dit(dit)
    trace = overload_trace(requests=requests,
                           num_classes=cfg.dit.num_classes, seed=seed,
                           base_rate=base_rate, burst_rate=burst_rate,
                           burst_start=burst_start, burst_len=burst_len,
                           priority_mix=priority_mix,
                           deadline_slack=deadline_slack)
    report: Dict = {
        "config": {"dit": dit, "policy": policy, "requests": requests,
                   "slots": slots, "steps": steps, "guidance": guidance,
                   "seed": seed, "base_rate": base_rate,
                   "burst_rate": burst_rate, "burst_start": burst_start,
                   "burst_len": burst_len,
                   "priority_mix": list(priority_mix),
                   "deadline_slack": list(deadline_slack),
                   "levels": _levels_config(levels)},
        "levels": [],
    }
    for level in levels:
        coll = MetricsCollector(labels={"level": level.name,
                                        "policy": policy})
        row, done, sched = serve_level(model, params, trace, level,
                                       policy=policy, slots=slots,
                                       steps=steps, guidance=guidance,
                                       collector=coll, repeats=repeats)
        row["by_class"] = summarize_by_class(done + sched.rejected)
        # quality run: shadow-audit EVERY step (wall time unused — this
        # run pays the full uncached forward, it is not a perf
        # measurement); the audited error is what this shedding level
        # costs in output quality
        coll_q = MetricsCollector(labels={"level": level.name,
                                          "policy": policy})
        _, done_q, _ = serve_level(model, params, trace, level,
                                   policy=policy, slots=slots,
                                   steps=steps, guidance=guidance,
                                   audit_fraction=1.0, collector=coll_q)
        # headline quality cost: mean end-to-end (eps-space) audit error
        # per GATED audited slot-step, from the exact per-request error
        # budgets (obs/audit.py AUDIT_ACC_KEYS) rather than the bucketed
        # histogram.  Each request's first step is a warm-up full
        # forward — exact by construction — so counting it would dilute
        # shorter (shed) budgets' measured cost, masking the
        # approximation the level actually buys its speed with.
        err_sum = sum(float((r.cache or {}).get("audit_err_sum", 0.0))
                      for r in done_q)
        asteps = sum(float((r.cache or {}).get("audit_steps", 0.0))
                     for r in done_q)
        gated = asteps - len(done_q)
        row["audited_slot_steps"] = asteps
        row["audit_err_mean"] = err_sum / asteps if asteps else 0.0
        row["quality_cost"] = err_sum / gated if gated > 0 else 0.0
        row["audit_err_p50"] = coll_q.quantile("audit_rel_err", 0.50)
        row["audit_err_p95"] = coll_q.quantile("audit_rel_err", 0.95)
        row["bound_violations"] = coll_q.totals().get(
            "bound_violations_total", 0.0)
        report["levels"].append(row)
    goodputs = [r["goodput"] for r in report["levels"]]
    costs = [r["quality_cost"] for r in report["levels"]]
    report["goodput_monotone"] = _monotone(goodputs)
    report["quality_cost_monotone"] = _monotone(costs)
    return report


def trajectory(*, dit: str = "dit-b2", policy: str = "fastcache",
               requests: int = 24, slots: int = 2, steps: int = 8,
               guidance: float = 4.0, seed: int = 0,
               base_rate: float = 0.1, burst_rate: float = 1.5,
               burst_start: int = 2, burst_len: int = 12,
               priority_mix: Sequence[int] = (0, 1, 1, 2),
               deadline_slack: Sequence[int] = (12, 20, 32),
               levels: Sequence[ShedLevel] = DEFAULT_LADDER) -> Dict:
    """One BENCH_serving.json entry for the overload suite: one point
    per shedding level (policy key ``<policy>@<level>``, so
    ``bench_check`` gates each level's ``model_step_ms`` independently)
    plus the monotonicity headlines."""
    report = benchmark(dit=dit, policy=policy, requests=requests,
                       slots=slots, steps=steps, guidance=guidance,
                       seed=seed, base_rate=base_rate,
                       burst_rate=burst_rate, burst_start=burst_start,
                       burst_len=burst_len, priority_mix=priority_mix,
                       deadline_slack=deadline_slack, levels=levels)
    points = []
    for r in report["levels"]:
        points.append({k: r[k] for k in
                       ("policy", "level", "steps_scale", "alpha",
                        "capacity_scale",
                        "offered", "finished", "rejected", "deadline_met",
                        "goodput", "preemptions", "latency_steps_p50",
                        "latency_steps_p99", "queue_wait_p50",
                        "queue_wait_p95", "model_step_ms", "steps_per_s",
                        "cache_ratio", "audited_slot_steps",
                        "audit_err_mean", "quality_cost", "audit_err_p50",
                        "audit_err_p95", "bound_violations")})
    return {
        "date": time.strftime("%Y-%m-%d"),
        "suite": "serving_overload",
        "config": report["config"],
        "points": points,
        "goodput_monotone": report["goodput_monotone"],
        "quality_cost_monotone": report["quality_cost_monotone"],
    }


def config_kwargs(config: Dict) -> Dict:
    """Map a committed entry's config record back to ``trajectory()``
    keyword arguments (the shed ladder round-trips through its JSON
    form)."""
    kw = {k: config[k] for k in ("dit", "policy", "requests", "slots",
                                 "steps", "guidance", "seed", "base_rate",
                                 "burst_rate", "burst_start", "burst_len",
                                 "priority_mix", "deadline_slack")
          if k in config}
    if "levels" in config:
        kw["levels"] = _levels_from_config(config["levels"])
    return kw


def fresh_for_check(baseline: Dict) -> Dict:
    """bench_check hook: measure a fresh overload point with the
    committed baseline entry's config (including its shed ladder)."""
    return trajectory(**config_kwargs(baseline.get("config", {})))


def write_trajectory(path: str, **kw) -> Dict:
    """Append one overload trajectory entry to the shared BENCH file."""
    return append_entry(path, trajectory(**kw))


def run() -> List[dict]:
    """benchmarks/run.py driver entry: compact CSV rows."""
    report = benchmark()
    rows = []
    for r in report["levels"]:
        rows.append({
            "name": (f"serving_overload/{report['config']['dit']}"
                     f"/{r['policy']}"),
            "us_per_call": r["model_step_ms"] * 1e3,
            "derived": (f"goodput={r['goodput']:.2f}"
                        f" deadline_met={r['deadline_met']}/{r['offered']}"
                        f" rejected={r['rejected']}"
                        f" p99_latency_steps={r['latency_steps_p99']:.0f}"
                        f" quality_cost={r['quality_cost']:.4f}"
                        f" cache_ratio={r['cache_ratio']:.3f}"),
        })
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dit", default="dit-b2")
    ap.add_argument("--policy", default="fastcache")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--guidance", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--base-rate", type=float, default=0.1)
    ap.add_argument("--burst-rate", type=float, default=1.5)
    ap.add_argument("--burst-start", type=int, default=2)
    ap.add_argument("--burst-len", type=int, default=12)
    ap.add_argument("--priority-mix", default="0,1,1,2",
                    help="comma list of priority classes requests draw "
                         "from uniformly (0 = most critical)")
    ap.add_argument("--deadline-slack", default="12,20,32",
                    help="comma list of deadline slacks (engine steps "
                         "past arrival) requests draw from uniformly")
    ap.add_argument("--json", default="",
                    help="write the JSON report here (default: stdout)")
    args = ap.parse_args()
    report = benchmark(
        dit=args.dit, policy=args.policy, requests=args.requests,
        slots=args.slots, steps=args.steps, guidance=args.guidance,
        seed=args.seed, base_rate=args.base_rate,
        burst_rate=args.burst_rate, burst_start=args.burst_start,
        burst_len=args.burst_len,
        priority_mix=[int(v) for v in args.priority_mix.split(",") if v],
        deadline_slack=[int(v) for v in args.deadline_slack.split(",")
                        if v])
    text = json.dumps(report, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        print(f"[serving_overload] report written to {args.json}")
    else:
        print(text)


if __name__ == "__main__":
    main()
