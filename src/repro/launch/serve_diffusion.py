"""Diffusion serving launcher: continuous-batching DiT sampling with
per-slot FastCache state (the image-generation twin of launch/serve.py).

    PYTHONPATH=src python -m repro.launch.serve_diffusion --arch dit-b2 \
        --reduced --requests 8 --slots 2 --steps 10 --policy fastcache

``--lockstep`` switches to the fixed-wave baseline (admit a full batch only
when every slot is free) for latency comparisons; ``--json`` emits the
summary as JSON.

``--steps-mix 20,50`` / ``--guidance-mix 1.0,4.0`` make the workload
heterogeneous: each request draws its own sampling plan (DDIM step budget,
guidance scale) from the mix and one engine batch serves them side by side
— the engine's plan tables are sized to the largest budget in the mix.
``--sched sjf`` switches the admission queue from FIFO to
shortest-job-first (smallest step budget among arrived requests first);
``--sched edf`` to earliest-deadline-first (needs ``--deadline-slack-mix``).

SLO control plane (``src/repro/serving/slo/``): ``--priority-mix 0,1,1,2``
and ``--deadline-slack-mix 12,20,32`` draw per-request priority classes
and deadlines; ``--burst-rate 2.0 --burst-start 5 --burst-len 20``
modulates the Poisson arrivals into a calm -> burst -> calm trace.
``--slo`` serves through ``SLOScheduler`` — strict-priority queues,
deadline-aware admission (``--on-miss reject|defer``), priority
preemption with bitwise device-side snapshot/resume (``--no-preempt``
disables), and, with ``--shed``, the watermark-hysteresis degradation
controller walking the default shed-level ladder under queue pressure
(``--shed-high``/``--shed-low`` watermarks, in ready-queue depth).  The
summary gains per-class latency/deadline/queue-wait breakdowns and
admission-rejection reasons.

``--no-cfg`` opts a guidance==1.0-only deployment into the static no-CFG
fast path: single-row slots, no materialized uncond half — the model batch
is S instead of 2S.

Observability (see ``src/repro/obs/``): ``--metrics-out prom.txt`` writes
the Prometheus text exposition at run end, ``--metrics-jsonl m.jsonl`` the
per-window JSONL trajectory (window size via ``--metrics-window N``, in
engine steps; default: one window at run end), and ``--trace-out t.json``
a Chrome/Perfetto trace of the run (open in ``ui.perfetto.dev``) with the
engine's spans (``engine.admit``, ``engine.step``, ``engine.harvest`` and
their parts), per-request admit/finish spans and per-slot denoise slices
annotated with the policy's cache decision.

``--audit-fraction 0.03125`` turns on the shadow-compute audit plane
(``src/repro/obs/audit.py``): a deterministic seeded fraction of serve
steps also runs the full uncached forward and measures cached-vs-true
error on device, checked against the policy's chi^2-predicted bound.
``--audit-baseline calib.npz`` arms the drift gauge against a PR 7
calibration recording; ``--audit-out audit.json`` writes per-request
error budgets plus the windowed drift/burn summary at run end.

``--mesh data,model`` serves through ``ShardedDiffusionEngine`` on a
``(data, model)`` device mesh (slots over ``data``, DiT weights over
``model``) with async host admission — disable the overlap with
``--sync-admission``.  Multi-device CPU runs need
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before launch:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
    PYTHONPATH=src python -m repro.launch.serve_diffusion --arch dit-b2 \\
        --reduced --requests 8 --slots 4 --steps 10 --mesh 4,2
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.configs.base import FastCacheConfig
from repro.core import CachedDiT, POLICIES
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.obs import (MetricsCollector, TraceRecorder, load_calibration,
                       validate_trace)
from repro.obs import audit as obs_audit
from repro.serving import (SCHED_POLICIES, AdmissionController,
                           DegradationController, DiffusionServingEngine,
                           ShardedDiffusionEngine, SLOScheduler,
                           piecewise_rate, poisson_trace,
                           summarize_by_class, summarize_by_steps)


def percentile(xs, p):
    return float(np.percentile(np.asarray(xs, np.float64), p)) if xs else -1.0


def parse_mesh(spec: str):
    """'data,model' (e.g. '4,2') -> (data, model) ints."""
    try:
        data, model = (int(v) for v in spec.split(","))
    except ValueError:
        raise SystemExit(f"--mesh expects 'data,model' ints, got {spec!r}")
    return data, model


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dit-b2")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10,
                    help="default DDIM steps per request")
    ap.add_argument("--guidance", type=float, default=4.0,
                    help="default guidance scale per request")
    ap.add_argument("--steps-mix", default="",
                    help="comma list of DDIM step budgets; each request "
                         "draws its own (e.g. 20,50)")
    ap.add_argument("--guidance-mix", default="",
                    help="comma list of guidance scales; each request "
                         "draws its own (e.g. 1.0,4.0)")
    ap.add_argument("--sched", default="fifo", choices=SCHED_POLICIES,
                    help="admission order among arrived requests (within "
                         "a priority class): FIFO, shortest-job-first, or "
                         "earliest-deadline-first")
    ap.add_argument("--priority-mix", default="",
                    help="comma list of priority classes requests draw "
                         "from uniformly (0 = most critical; empty = all "
                         "class 0)")
    ap.add_argument("--deadline-slack-mix", default="",
                    help="comma list of deadline slacks (engine steps "
                         "past arrival) requests draw from uniformly "
                         "(empty = no deadlines)")
    ap.add_argument("--burst-rate", type=float, default=0.0,
                    help="burst arrival rate; with --burst-len > 0 the "
                         "trace is calm (--rate) -> burst -> calm")
    ap.add_argument("--burst-start", type=int, default=0,
                    help="engine step the burst begins at")
    ap.add_argument("--burst-len", type=int, default=0,
                    help="burst duration in engine steps (0 = no burst)")
    ap.add_argument("--slo", action="store_true",
                    help="serve through the SLO control plane "
                         "(SLOScheduler): strict-priority queues, "
                         "deadline-aware admission, priority preemption "
                         "with device-side snapshot/resume")
    ap.add_argument("--on-miss", default="reject",
                    choices=("reject", "defer"),
                    help="--slo: what deadline-aware admission does with "
                         "a request predicted to miss: reject it, or "
                         "defer and re-test later")
    ap.add_argument("--no-preempt", action="store_true",
                    help="--slo: disable priority preemption")
    ap.add_argument("--shed", action="store_true",
                    help="--slo: enable the graceful-degradation "
                         "controller (default shed-level ladder, "
                         "watermark hysteresis on ready-queue depth)")
    ap.add_argument("--shed-high", type=int, default=8,
                    help="--shed: queue depth escalating one shed level "
                         "when sustained")
    ap.add_argument("--shed-low", type=int, default=2,
                    help="--shed: queue depth de-escalating one shed "
                         "level when sustained")
    ap.add_argument("--no-cfg", action="store_true",
                    help="static no-CFG fast path for guidance==1.0-only "
                         "deployments: single-row slots, no materialized "
                         "uncond half (model batch S instead of 2S); "
                         "requires --guidance 1.0 and an all-1.0 "
                         "--guidance-mix")
    ap.add_argument("--policy", default="fastcache", choices=POLICIES)
    ap.add_argument("--token-merge-ratio", type=float, default=1.0,
                    help="serving-path token compression: keep "
                         "ceil(ratio * window) cluster centers per window "
                         "of tokens before the cache policy runs "
                         "(core/token_reduce.py); 1.0 disables the stage "
                         "(bitwise-identical to merge-off)")
    ap.add_argument("--token-merge-window", type=int, default=16,
                    help="token-compression window size w; the DiT token "
                         "count must be divisible by it")
    ap.add_argument("--rate", type=float, default=0.5,
                    help="Poisson arrival rate (requests per engine step)")
    ap.add_argument("--lockstep", action="store_true",
                    help="fixed-wave baseline instead of continuous admission")
    ap.add_argument("--mesh", default="",
                    help="serve sharded on a 'data,model' mesh (e.g. 4,2); "
                         "empty = single-device engine")
    ap.add_argument("--sync-admission", action="store_true",
                    help="sharded engine only: disable the async admission/"
                         "harvest overlap (sync per-completion fetches)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--metrics-out", default="",
                    help="write the Prometheus text exposition here at "
                         "run end")
    ap.add_argument("--metrics-jsonl", default="",
                    help="write the per-window JSONL metrics trajectory "
                         "here at run end")
    ap.add_argument("--metrics-window", type=int, default=0,
                    help="harvest a metrics window every N engine steps "
                         "(each window close is one device sync); 0 = one "
                         "window at run end only")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome/Perfetto trace JSON of the run "
                         "here (engine spans, per-request spans, per-slot "
                         "denoise slices with cache decisions)")
    ap.add_argument("--audit-fraction", type=float, default=0.0,
                    help="shadow-audit this fraction of serve steps "
                         "(deterministic seeded schedule; 0 disables the "
                         "audit plane entirely — it is statically dead "
                         "code in the jitted step)")
    ap.add_argument("--audit-seed", type=int, default=0,
                    help="seed for the audit sampling schedule")
    ap.add_argument("--audit-baseline", default="",
                    help="calibration .npz (obs.calibration) to arm the "
                         "audit_drift_ratio gauge: measured per-layer "
                         "cache error vs the nocache run's natural "
                         "inter-step deltas")
    ap.add_argument("--audit-out", default="",
                    help="write the audit report JSON (per-request error "
                         "budgets, windowed drift/burn summary) here at "
                         "run end")
    args = ap.parse_args()
    if args.audit_out and args.audit_fraction <= 0.0:
        raise SystemExit("--audit-out needs --audit-fraction > 0")

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced:
        cfg = cfg.replace(dtype="float32")
    if cfg.dit is None:
        raise SystemExit(f"{cfg.name} is not a DiT — nothing to diffuse")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if not 0.0 < args.token_merge_ratio <= 1.0:
        raise SystemExit(f"--token-merge-ratio must be in (0, 1], got "
                         f"{args.token_merge_ratio}")
    fc = FastCacheConfig(merge_enabled=args.token_merge_ratio < 1.0,
                         merge_ratio=args.token_merge_ratio,
                         merge_window=args.token_merge_window)
    runner = CachedDiT(model, fc, policy=args.policy)
    steps_mix = [int(v) for v in args.steps_mix.split(",") if v.strip()]
    guidance_mix = [float(v) for v in args.guidance_mix.split(",")
                    if v.strip()]
    # plan tables must fit the largest step budget in the workload
    max_steps = max(steps_mix + [args.steps])
    if args.no_cfg and (args.guidance != 1.0
                        or any(g != 1.0 for g in guidance_mix)):
        raise SystemExit("--no-cfg serves guidance==1.0 only; pass "
                         "--guidance 1.0 and an all-1.0 --guidance-mix")
    # the audit plane folds into the device metrics pytree, so auditing
    # implies the metrics plane (and a collector to harvest drift/burn)
    want_metrics = bool(args.metrics_out or args.metrics_jsonl
                        or args.audit_fraction > 0.0)
    collector = MetricsCollector(
        labels={"policy": args.policy, "arch": args.arch},
        window_steps=args.metrics_window or None) if want_metrics else None
    if collector is not None and args.audit_baseline:
        calib = load_calibration(args.audit_baseline)
        collector.set_audit_context(baseline=calib["errors_mean"])
    tracer = (TraceRecorder(capture_slots=True) if args.trace_out
              else None)
    if args.mesh:
        data, tp = parse_mesh(args.mesh)
        engine = ShardedDiffusionEngine(
            runner, params, max_slots=args.slots, num_steps=args.steps,
            guidance_scale=args.guidance, max_steps=max_steps,
            mesh=make_serving_mesh(data, tp),
            async_admission=not args.sync_admission,
            cfg_rows=not args.no_cfg, collector=collector, tracer=tracer,
            audit_fraction=args.audit_fraction, audit_seed=args.audit_seed)
    else:
        engine = DiffusionServingEngine(runner, params,
                                        max_slots=args.slots,
                                        num_steps=args.steps,
                                        guidance_scale=args.guidance,
                                        max_steps=max_steps,
                                        cfg_rows=not args.no_cfg,
                                        collector=collector, tracer=tracer,
                                        audit_fraction=args.audit_fraction,
                                        audit_seed=args.audit_seed)
    priority_mix = [int(v) for v in args.priority_mix.split(",")
                    if v.strip()]
    slack_mix = [int(v) for v in args.deadline_slack_mix.split(",")
                 if v.strip()]
    rate_fn = None
    if args.burst_len > 0:
        if args.burst_rate <= 0.0:
            raise SystemExit("--burst-len needs --burst-rate > 0")
        rate_fn = piecewise_rate([(args.burst_start, args.rate),
                                  (args.burst_start + args.burst_len,
                                   args.burst_rate),
                                  (10 ** 9, args.rate)])
    trace = poisson_trace(args.requests, args.rate, seed=args.seed,
                          num_classes=cfg.dit.num_classes,
                          steps_mix=steps_mix or None,
                          guidance_mix=guidance_mix or None,
                          rate_fn=rate_fn,
                          priority_mix=priority_mix or None,
                          deadline_slack_mix=slack_mix or None)
    rejected = []
    if args.slo:
        if args.lockstep:
            raise SystemExit("--slo drives continuous admission; drop "
                             "--lockstep")
        admission = AdmissionController(engine, on_miss=args.on_miss,
                                        collector=collector)
        controller = DegradationController(
            high_watermark=args.shed_high, low_watermark=args.shed_low,
            collector=collector) if args.shed else None
        slo = SLOScheduler(engine, sched_policy=args.sched,
                           admission=admission, controller=controller,
                           preempt=not args.no_preempt,
                           collector=collector)
        t0 = time.perf_counter()
        done = slo.run(trace)
        dt = time.perf_counter() - t0
        rejected = slo.rejected
    else:
        t0 = time.perf_counter()
        done = engine.run(trace, lockstep=args.lockstep,
                          sched_policy=args.sched)
        dt = time.perf_counter() - t0

    lats = [r.latency_steps for r in done]
    summary = {
        "mode": "lockstep" if args.lockstep else "continuous",
        "sched_policy": args.sched,
        "topology": (engine.topology() if args.mesh
                     else {"data": 1, "model": 1, "devices": 1}),
        "async_admission": bool(args.mesh) and not args.sync_admission,
        "cfg_rows": not args.no_cfg,
        "policy": args.policy,
        "requests": len(done),
        "steps_mix": steps_mix or [args.steps],
        "guidance_mix": guidance_mix or [args.guidance],
        "engine_steps": engine.clock,
        "model_steps": engine.model_steps,
        "wall_s": dt,
        "requests_per_s": len(done) / dt if dt else 0.0,
        "latency_steps_p50": percentile(lats, 50),
        "latency_steps_p95": percentile(lats, 95),
        "latency_by_steps": summarize_by_steps(done + rejected),
        "by_class": summarize_by_class(done + rejected),
        "cache": engine.cache_stats(),
        "token_merge": {"ratio": args.token_merge_ratio,
                        "window": args.token_merge_window,
                        "active": runner.reducer is not None},
    }
    if args.slo:
        met = sum(1 for r in done
                  if r.deadline_step is None
                  or r.finish_step <= r.deadline_step)
        summary["slo"] = {
            "on_miss": args.on_miss,
            "preempt": not args.no_preempt,
            "shed": bool(args.shed),
            "shed_level": (controller.level.name if controller is not None
                           else None),
            "rejected": len(rejected),
            "deadline_met": met,
            "goodput": met / len(trace) if trace else 0.0,
            "preemptions": sum(r.preemptions for r in done),
        }
    if collector is not None:
        collector.set_gauge("run_wall_seconds", dt)
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                f.write(collector.to_prometheus())
        if args.metrics_jsonl:
            with open(args.metrics_jsonl, "w") as f:
                f.write(collector.to_jsonl())
    if args.audit_fraction > 0.0:
        report = obs_audit.audit_report(done, fraction=args.audit_fraction,
                                        bound=runner.audit_bound(),
                                        collector=collector)
        summary["audit"] = {k: report[k] for k in
                            ("audit_fraction", "predicted_bound",
                             "violations_total")}
        if args.audit_out:
            with open(args.audit_out, "w") as f:
                json.dump(report, f, indent=2)
    if tracer is not None:
        doc = tracer.to_json()
        validate_trace(doc)
        with open(args.trace_out, "w") as f:
            json.dump(doc, f)
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        print(f"[serve-diffusion] {summary['mode']} sched={args.sched} "
              f"policy={args.policy}: "
              f"{len(done)} requests in {dt:.2f}s "
              f"({summary['requests_per_s']:.2f} req/s incl. compile), "
              f"{engine.clock} engine steps")
        print(f"[serve-diffusion] latency (steps): "
              f"p50={summary['latency_steps_p50']:.0f} "
              f"p95={summary['latency_steps_p95']:.0f}")
        print(f"[serve-diffusion] cache: {engine.cache_stats()}")


if __name__ == "__main__":
    main()
