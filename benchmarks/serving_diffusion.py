"""Serving benchmark: lockstep (fixed-wave) vs continuous-admission batching
for DiT sampling under a Poisson arrival trace.

Both modes serve the SAME request trace through the same engine; the only
difference is the admission policy — lockstep admits a new wave only when
every slot is free (a batched ``sample()`` loop), continuous admits into any
free slot mid-flight, which the per-slot FastCache state makes safe.  Late
arrivals therefore stop paying for their whole wave's completion, which is
the p95-latency win this benchmark measures.

    PYTHONPATH=src python -m benchmarks.serving_diffusion [--json out.json]

Emits a JSON report (stdout or --json path) with per-mode throughput,
p50/p95 request latency (engine-step clock + measured wall time per step)
and engine-level cache-ratio stats; also runnable through benchmarks/run.py
(suite name ``serving``) as compact CSV rows.

``--mesh 1x1,4x1,4x2`` adds a topology sweep: the SAME trace is served
through the single-device engine and through ``ShardedDiffusionEngine`` on
each listed ``(data, model)`` mesh (async host admission), reporting one
JSON row per topology — p50/p95 latency, steps/sec, cache ratio, and
max-abs-diff of every request's latents against the single-device run
(bitwise parity => 0.0).  Multi-device topologies on CPU need
``XLA_FLAGS=--xla_force_host_platform_device_count=N``; the ``bench-serve``
driver row (suite name ``serving_sharded``) sets that in a subprocess.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmarks.common import build_dit
from repro.configs.base import FastCacheConfig
from repro.core import CachedDiT, registered_policies
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import DEFAULT_AUDIT_FRACTION, MetricsCollector
from repro.serving import (DiffusionRequest, DiffusionServingEngine,
                           ShardedDiffusionEngine, make_serving_mesh,
                           poisson_trace)


def _fresh_trace(trace: List[DiffusionRequest]) -> List[DiffusionRequest]:
    """Engines mutate requests in place; each mode gets its own copies."""
    return [dataclasses.replace(r, latents=None, cache=None, admit_step=-1,
                                finish_step=-1, done=False,
                                queue_wait_steps=-1, reject_reason=None,
                                preemptions=0, steps_done=0, snapshot=None)
            for r in trace]


def serve_once(model, params, trace, *, policy: str, slots: int, steps: int,
               guidance: float, lockstep: bool, topology=None,
               async_admission: bool = True, max_steps=None,
               sched_policy: str = "fifo", collector=None,
               enable_metrics: bool = True, audit_fraction: float = 0.0,
               audit_seed: int = 0, fc: FastCacheConfig = None
               ) -> Tuple[Dict, List[DiffusionRequest]]:
    """One engine run over a fresh copy of ``trace``; returns (result row,
    finished requests).  ``topology`` (data, model) != (1, 1) serves
    through the sharded engine on that mesh.  ``max_steps`` sizes the plan
    tables for heterogeneous traces (defaults to ``steps``);
    ``sched_policy`` picks the admission order (fifo / sjf);
    ``collector``/``enable_metrics`` thread the obs plane through the
    engine (``enable_metrics=False`` traces a metrics-free step, the
    A/B baseline for the telemetry-overhead row in the trajectory);
    ``audit_fraction > 0`` arms the shadow-compute audit plane on that
    fraction of serve steps (requires metrics); ``fc`` overrides the
    runner's FastCacheConfig (e.g. to switch the token-merge stage on)."""
    runner = CachedDiT(model, fc or FastCacheConfig(), policy=policy)
    if topology and tuple(topology) != (1, 1):
        data, tp = topology
        engine = ShardedDiffusionEngine(
            runner, params, max_slots=slots, num_steps=steps,
            guidance_scale=guidance, max_steps=max_steps,
            mesh=make_serving_mesh(data, tp),
            async_admission=async_admission, collector=collector,
            enable_metrics=enable_metrics, audit_fraction=audit_fraction,
            audit_seed=audit_seed)
    else:
        engine = DiffusionServingEngine(runner, params, max_slots=slots,
                                        num_steps=steps,
                                        guidance_scale=guidance,
                                        max_steps=max_steps,
                                        collector=collector,
                                        enable_metrics=enable_metrics,
                                        audit_fraction=audit_fraction,
                                        audit_seed=audit_seed)
    reqs = _fresh_trace(trace)
    # warm the jitted serve_step so wall-time excludes compilation, then
    # rewind the clock so the trace's absolute arrival steps line up
    warm = _fresh_trace(trace[:1])
    for r in warm:
        r.arrival_step = 0
    engine.run(warm)
    engine.reset_clock()
    t0 = time.perf_counter()
    done = engine.run(reqs, lockstep=lockstep, sched_policy=sched_policy)
    wall = time.perf_counter() - t0
    assert len(done) == len(trace), (len(done), len(trace))
    lats = np.array([r.latency_steps for r in done], np.float64)
    # per-MODEL-step time: idle clock ticks cost no wall time, so dividing
    # by engine.clock would flatter whichever mode idles more
    model_step_ms = wall / max(1, engine.model_steps) * 1e3
    res = {
        "mode": "lockstep" if lockstep else "continuous",
        "sched_policy": sched_policy,
        "policy": policy,
        "topology": {"data": 1, "model": 1, "devices": 1},
        "requests": len(done),
        "engine_steps": engine.clock,
        "model_steps": engine.model_steps,
        "wall_s": wall,
        "requests_per_s": len(done) / wall if wall else 0.0,
        "steps_per_s": engine.model_steps / wall if wall else 0.0,
        "model_step_ms": model_step_ms,
        "latency_steps_p50": float(np.percentile(lats, 50)),
        "latency_steps_p95": float(np.percentile(lats, 95)),
        "cache": engine.cache_stats(),
    }
    if isinstance(engine, ShardedDiffusionEngine):
        res["topology"] = engine.topology()
        res["async_admission"] = engine.async_admission
    return res, done


def benchmark(*, dit: str = "dit-b2", policies=("nocache", "fastcache"),
              requests: int = 10, slots: int = 2, steps: int = 8,
              guidance: float = 4.0, rate: float = 0.25,
              seed: int = 0) -> Dict:
    cfg, model, params = build_dit(dit)
    trace = poisson_trace(requests, rate, seed=seed,
                          num_classes=cfg.dit.num_classes)
    report: Dict = {
        "config": {"dit": dit, "requests": requests, "slots": slots,
                   "steps": steps, "guidance": guidance,
                   "poisson_rate": rate, "seed": seed},
        "runs": [],
    }
    for policy in policies:
        for lockstep in (True, False):
            res, _ = serve_once(model, params, trace, policy=policy,
                                slots=slots, steps=steps, guidance=guidance,
                                lockstep=lockstep)
            report["runs"].append(res)
    # headline: continuous must beat lockstep on p95 under queueing pressure
    for policy in policies:
        runs = {r["mode"]: r for r in report["runs"]
                if r["policy"] == policy}
        report[f"p95_speedup_steps_{policy}"] = (
            runs["lockstep"]["latency_steps_p95"]
            / max(runs["continuous"]["latency_steps_p95"], 1e-9))
    return report


def trajectory(*, dit: str = "dit-b2", policies=None, requests: int = 6,
               slots: int = 2, steps: int = 8, guidance: float = 4.0,
               rate: float = 0.25, seed: int = 0, repeats: int = 3,
               merge_ratio: float = 0.5, merge_window: int = 16) -> Dict:
    """One perf-trajectory entry: every registered cache policy served
    through the continuous engine with the metrics plane ON (a live
    ``MetricsCollector``, harvested at run end) and OFF (the A/B
    baseline) — so the committed ``BENCH_serving.json`` carries both the
    per-policy serving numbers and the telemetry-overhead headline.

    A single short CPU run is wall-clock noisy, so each (policy, mode)
    pair is served ``repeats`` times interleaved (off/on/audit ... to
    cancel clock drift) and scored by its best wall time; the headline
    ``metrics_overhead_pct`` further aggregates best-run model-step wall
    across ALL policies, which is what the < 5% acceptance bar is
    checked against.

    Quality columns (the audit plane, PR 8): every policy is additionally
    served once with ``audit_fraction=1.0`` — every step shadow-audited —
    and the per-policy ``audit_err_p50/p95`` quantiles of the measured
    cached-vs-true relative error land next to its perf numbers, plus
    ``bound_violations`` against the policy's chi^2-predicted bound.  The
    cost of auditing at the production ``DEFAULT_AUDIT_FRACTION`` is
    measured separately (``model_step_ms_audit``) and aggregated into the
    ``audit_overhead_pct`` headline (vs the metrics-on baseline — the <5%
    acceptance bar).

    Token-compression columns: every policy is additionally served with
    the serving-path merge stage ON (``merge_ratio`` centers kept per
    ``merge_window`` tokens, the same repeats/best-wall protocol) —
    ``model_step_ms_merge`` next to the merge-off ``model_step_ms``
    quantifies the reduced-grid speedup, and a fully-audited merge run
    reports ``merge_audit_err_p50/p95``, the realized end-to-end error of
    merge+cache vs the uncached full-resolution forward."""
    policies = tuple(policies) if policies else registered_policies()
    cfg, model, params = build_dit(dit)
    trace = poisson_trace(requests, rate, seed=seed,
                          num_classes=cfg.dit.num_classes)
    entry: Dict = {
        "date": time.strftime("%Y-%m-%d"),
        "suite": "serving",
        "config": {"dit": dit, "requests": requests, "slots": slots,
                   "steps": steps, "guidance": guidance,
                   "poisson_rate": rate, "seed": seed, "repeats": repeats,
                   "merge_ratio": merge_ratio,
                   "merge_window": merge_window, "mode": "continuous"},
        "points": [],
    }
    fc_merge = FastCacheConfig(merge_enabled=True, merge_ratio=merge_ratio,
                               merge_window=merge_window)
    wall_on = wall_off = wall_audit = 0.0
    steps_on = steps_off = steps_audit = 0
    for policy in policies:
        res_off = res_on = res_audit = res_merge = collector = None
        for _ in range(max(1, repeats)):
            off, _ = serve_once(model, params, trace, policy=policy,
                                slots=slots, steps=steps,
                                guidance=guidance, lockstep=False,
                                enable_metrics=False)
            coll = MetricsCollector(labels={"policy": policy, "dit": dit})
            on, _ = serve_once(model, params, trace, policy=policy,
                               slots=slots, steps=steps,
                               guidance=guidance, lockstep=False,
                               collector=coll)
            aud, _ = serve_once(model, params, trace, policy=policy,
                                slots=slots, steps=steps,
                                guidance=guidance, lockstep=False,
                                collector=MetricsCollector(),
                                audit_fraction=DEFAULT_AUDIT_FRACTION)
            mrg, _ = serve_once(model, params, trace, policy=policy,
                                slots=slots, steps=steps,
                                guidance=guidance, lockstep=False,
                                collector=MetricsCollector(),
                                fc=fc_merge)
            if res_off is None or off["wall_s"] < res_off["wall_s"]:
                res_off = off
            if res_on is None or on["wall_s"] < res_on["wall_s"]:
                res_on, collector = on, coll
            if res_audit is None or aud["wall_s"] < res_audit["wall_s"]:
                res_audit = aud
            if res_merge is None or mrg["wall_s"] < res_merge["wall_s"]:
                res_merge = mrg
        totals = collector.totals()
        # quality row: audit EVERY step once (wall time unused — this run
        # pays the full shadow forward, it is not a perf measurement)
        coll_q = MetricsCollector(labels={"policy": policy, "dit": dit})
        _, _ = serve_once(model, params, trace, policy=policy, slots=slots,
                          steps=steps, guidance=guidance, lockstep=False,
                          collector=coll_q, audit_fraction=1.0)
        q_totals = coll_q.totals()
        # merge quality row: the audit plane's shadow forward stays at
        # full resolution, so the audited error IS merge+cache vs nocache
        coll_m = MetricsCollector(labels={"policy": policy, "dit": dit})
        _, _ = serve_once(model, params, trace, policy=policy, slots=slots,
                          steps=steps, guidance=guidance, lockstep=False,
                          collector=coll_m, audit_fraction=1.0, fc=fc_merge)
        m_totals = coll_m.totals()
        wall_on += res_on["wall_s"]
        wall_off += res_off["wall_s"]
        wall_audit += res_audit["wall_s"]
        steps_on += res_on["model_steps"]
        steps_off += res_off["model_steps"]
        steps_audit += res_audit["model_steps"]
        entry["points"].append({
            "policy": policy,
            "requests": res_on["requests"],
            "latency_steps_p50": res_on["latency_steps_p50"],
            "latency_steps_p95": res_on["latency_steps_p95"],
            "steps_per_s": res_on["steps_per_s"],
            "model_step_ms": res_on["model_step_ms"],
            "model_step_ms_metrics_off": res_off["model_step_ms"],
            "model_step_ms_audit": res_audit["model_step_ms"],
            "cache_ratio": res_on["cache"]["block_cache_ratio"],
            "serve_steps_total": totals.get("serve_steps_total", 0.0),
            "cache_step_reuses_total": totals.get(
                "cache_step_reuses_total", 0.0),
            "audit_err_p50": coll_q.quantile("audit_rel_err", 0.50),
            "audit_err_p95": coll_q.quantile("audit_rel_err", 0.95),
            "bound_violations": q_totals.get("bound_violations_total",
                                             0.0),
            "model_step_ms_merge": res_merge["model_step_ms"],
            "merge_speedup": (res_on["model_step_ms"]
                              / max(res_merge["model_step_ms"], 1e-9)),
            "tokens_kept_total": m_totals.get("tokens_kept_total", 0.0),
            "tokens_merged_total": m_totals.get("tokens_merged_total",
                                                0.0),
            "merge_audit_err_p50": coll_m.quantile("audit_rel_err", 0.50),
            "merge_audit_err_p95": coll_m.quantile("audit_rel_err", 0.95),
        })
    ms_on = wall_on / max(1, steps_on) * 1e3
    ms_off = wall_off / max(1, steps_off) * 1e3
    ms_audit = wall_audit / max(1, steps_audit) * 1e3
    entry["model_step_ms_on"] = ms_on
    entry["model_step_ms_off"] = ms_off
    entry["metrics_overhead_pct"] = (ms_on - ms_off) / ms_off * 100.0 \
        if ms_off else 0.0
    # audit overhead is measured against the metrics-on baseline (the
    # audit plane requires the metrics plane) at the production fraction
    entry["audit_fraction"] = DEFAULT_AUDIT_FRACTION
    entry["model_step_ms_audit"] = ms_audit
    entry["audit_overhead_pct"] = (ms_audit - ms_on) / ms_on * 100.0 \
        if ms_on else 0.0
    return entry


def _entry_key(entry: Dict) -> Tuple[str, str, str]:
    """Dedupe identity for a trajectory entry: same suite + same day +
    same benchmark config (canonical JSON) means a re-run, not a new
    point.  Entries written before suites shared the BENCH file carry no
    ``suite`` field and default to ``serving``."""
    return (entry.get("suite", "serving"), entry.get("date", ""),
            json.dumps(entry.get("config", {}), sort_keys=True))


def append_entry(path: str, entry: Dict) -> Dict:
    """Append one trajectory entry to the BENCH file at ``path`` (created
    if absent), preserving prior entries so the file accumulates one
    point per PR.  Re-running on the same day with the same (suite,
    config) REPLACES that entry in place instead of appending a duplicate
    — the trajectory stays one point per (suite, date, config), so
    iterating on a PR does not pad the committed history.  Shared by
    every suite that writes into the serving BENCH file (``serving``
    here, ``serving_overload`` in benchmarks/serving_overload.py)."""
    doc = {"schema": 1, "suite": "serving", "entries": []}
    try:
        with open(path) as f:
            prev = json.load(f)
        if prev.get("schema") == 1 and isinstance(prev.get("entries"),
                                                  list):
            doc = prev
    except (OSError, ValueError):
        pass
    key = _entry_key(entry)
    # drop any same-key predecessors, then append: the fresh entry is
    # always entries[-1] among its suite and entries stay date-ordered
    # (the key includes today's date, so only today's re-runs are
    # replaced)
    doc["entries"] = [e for e in doc["entries"] if _entry_key(e) != key]
    doc["entries"].append(entry)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return doc


def config_kwargs(config: Dict) -> Dict:
    """Map a committed entry's config record back to ``trajectory()``
    keyword arguments (``poisson_rate`` -> ``rate``; ``mode`` is
    implied)."""
    kw = {k: config[k] for k in ("dit", "requests", "slots", "steps",
                                 "guidance", "seed", "repeats",
                                 "merge_ratio", "merge_window")
          if k in config}
    if "poisson_rate" in config:
        kw["rate"] = config["poisson_rate"]
    return kw


def fresh_for_check(baseline: Dict) -> Dict:
    """bench_check hook: measure a fresh trajectory point with the
    committed baseline entry's config and policy set."""
    policies = tuple(p["policy"] for p in baseline.get("points", []))
    return trajectory(policies=policies or None,
                      **config_kwargs(baseline.get("config", {})))


def write_trajectory(path: str, **kw) -> Dict:
    """Append one ``trajectory()`` entry to the BENCH file at ``path``."""
    return append_entry(path, trajectory(**kw))


def parse_topologies(spec: str) -> List[tuple]:
    """'1x1,4x1,4x2' -> [(1, 1), (4, 1), (4, 2)] (data x model)."""
    out = []
    for part in spec.split(","):
        if not part.strip():
            continue
        d, m = part.lower().split("x")
        out.append((int(d), int(m)))
    return out


def benchmark_topologies(*, topologies, dit: str = "dit-b2",
                         policies=("fastcache",), requests: int = 8,
                         slots: int = 4, steps: int = 8,
                         guidance: float = 4.0, rate: float = 0.25,
                         seed: int = 0) -> Dict:
    """Serve the SAME Poisson trace through every listed (data, model)
    topology — (1, 1) is the single-device ``DiffusionServingEngine``,
    everything else ``ShardedDiffusionEngine`` with async admission — for
    every listed policy, reporting one row per (policy, topology).
    Parity fields (``max_abs_diff_vs_single``,
    ``schedule_identical_vs_single``) are emitted only when that policy's
    (1, 1) run is in the sweep to compare against.  Topologies that need
    more devices than available, or that the engine's numerics self-check
    refuses, are reported as skipped rather than failing the sweep."""
    import jax
    cfg, model, params = build_dit(dit)
    trace = poisson_trace(requests, rate, seed=seed,
                          num_classes=cfg.dit.num_classes)
    report: Dict = {
        "config": {"dit": dit, "policies": list(policies),
                   "requests": requests, "slots": slots, "steps": steps,
                   "guidance": guidance, "poisson_rate": rate,
                   "seed": seed, "device_count": jax.device_count()},
        "topologies": [],
    }
    for policy in policies:
        # parity baseline: strictly the single-device (1, 1) run
        baseline: Dict[str, Dict] = {}
        for topo in topologies:
            need = topo[0] * topo[1]
            topo_info = {"data": topo[0], "model": topo[1],
                         "devices": need}
            if need > jax.device_count():
                report["topologies"].append(
                    {"policy": policy, "topology": topo_info,
                     "skipped": f"needs {need} devices, have "
                                f"{jax.device_count()}"})
                continue
            try:
                res, done = serve_once(model, params, trace, policy=policy,
                                       slots=slots, steps=steps,
                                       guidance=guidance, lockstep=False,
                                       topology=topo)
            except RuntimeError as e:
                # e.g. the engine's startup numerics self-check refusing a
                # mesh the backend's partitioner miscompiles
                report["topologies"].append(
                    {"policy": policy, "topology": topo_info,
                     "skipped": str(e)})
                continue
            sched = {r.rid: (r.admit_step, r.finish_step) for r in done}
            if tuple(topo) == (1, 1):
                baseline = {"latents": {r.rid: r.latents for r in done},
                            "sched": sched}
                res["max_abs_diff_vs_single"] = 0.0
                res["schedule_identical_vs_single"] = True
            elif baseline:
                # scheduling parity is exact (host bookkeeping is
                # topology-independent); latents are compared by
                # max-abs-diff because XLA:CPU gemms are batch-shape
                # sensitive — a 2-row and an 8-row matmul differ in the
                # last bits, which the recursive DDIM update then
                # amplifies (bitwise-parity regime: see
                # tests/test_sharded_serving.py)
                res["max_abs_diff_vs_single"] = max(
                    float(np.max(np.abs(np.asarray(r.latents)
                                        - baseline["latents"][r.rid])))
                    for r in done)
                res["schedule_identical_vs_single"] = (
                    sched == baseline["sched"])
            report["topologies"].append(res)
    return report


def run() -> List[dict]:
    """benchmarks/run.py driver entry: compact CSV rows."""
    report = benchmark()
    rows = []
    for r in report["runs"]:
        rows.append({
            "name": (f"serving/{report['config']['dit']}/{r['policy']}"
                     f"/{r['mode']}"),
            "us_per_call": r["model_step_ms"] * 1e3,
            "derived": (f"p95_latency_steps={r['latency_steps_p95']:.0f}"
                        f" p50={r['latency_steps_p50']:.0f}"
                        f" req_per_s={r['requests_per_s']:.2f}"
                        f" cache_ratio="
                        f"{r['cache']['block_cache_ratio']:.3f}"),
        })
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dit", default="dit-b2")
    ap.add_argument("--policies", default="nocache,fastcache")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--guidance", type=float, default=4.0)
    ap.add_argument("--rate", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="topology sweep instead of the mode comparison: "
                         "comma list of DATAxMODEL meshes, e.g. 1x1,4x1,4x2")
    ap.add_argument("--json", default="",
                    help="write the JSON report here (default: stdout)")
    args = ap.parse_args()
    if args.mesh:
        report = benchmark_topologies(
            topologies=parse_topologies(args.mesh), dit=args.dit,
            policies=tuple(p for p in args.policies.split(",") if p),
            requests=args.requests, slots=args.slots, steps=args.steps,
            guidance=args.guidance, rate=args.rate, seed=args.seed)
    else:
        report = benchmark(dit=args.dit,
                           policies=tuple(p for p in
                                          args.policies.split(",") if p),
                           requests=args.requests, slots=args.slots,
                           steps=args.steps, guidance=args.guidance,
                           rate=args.rate, seed=args.seed)
    text = json.dumps(report, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        print(f"[serving_diffusion] report written to {args.json}")
    else:
        print(text)


if __name__ == "__main__":
    main()
