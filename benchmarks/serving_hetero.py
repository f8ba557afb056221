"""Heterogeneous sampling-plan serving benchmark: mixed-step-budget Poisson
traffic (e.g. 20-step and 50-step requests at different guidance scales)
through one continuous-batching engine, FIFO vs shortest-job-first.

Every request carries its own ``SamplingPlan`` (DDIM step budget + guidance
scale drawn from the mix), and one engine batch serves them side by side —
the per-slot plan tables make a 20-step job next to a 50-step job exact,
so the scheduler policy is the only variable.  SJF should cut the short
jobs' queueing latency (they stop waiting behind long residents' slots)
at the cost of long-job tail latency; this benchmark measures exactly that
trade plus the cache behavior per step budget (cache schedules are a
function of the request's budget — SmoothCache / Learning-to-Cache — so
the per-budget ratio is the serving-relevant number, not the pooled one).

    PYTHONPATH=src python -m benchmarks.serving_hetero [--json out.json]
    PYTHONPATH=src python -m benchmarks.serving_hetero --steps-mix 20,50

Emits a JSON report (stdout or --json path): one row per scheduling
policy with overall p50/p95 latency plus, per step budget in the mix,
request count, p50/p95 latency and the cache ratio harvested from the
requests' own request-scoped counters (``req.cache``).  Also runnable
through benchmarks/run.py (suite name ``serving_hetero``) as compact CSV
rows.
"""
from __future__ import annotations

import argparse
import json
from typing import Dict, List, Sequence

from benchmarks.common import build_dit
from benchmarks.serving_diffusion import serve_once
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import poisson_trace, summarize_by_steps


def benchmark(*, dit: str = "dit-b2", policy: str = "fastcache",
              requests: int = 12, slots: int = 2,
              steps_mix: Sequence[int] = (4, 8),
              guidance_mix: Sequence[float] = (1.0, 4.0),
              rate: float = 0.25, seed: int = 0) -> Dict:
    cfg, model, params = build_dit(dit)
    trace = poisson_trace(requests, rate, seed=seed,
                          num_classes=cfg.dit.num_classes,
                          steps_mix=steps_mix, guidance_mix=guidance_mix)
    max_steps = max(steps_mix)
    report: Dict = {
        "config": {"dit": dit, "policy": policy, "requests": requests,
                   "slots": slots, "steps_mix": list(steps_mix),
                   "guidance_mix": list(guidance_mix),
                   "poisson_rate": rate, "seed": seed},
        "runs": [],
    }
    for sched in ("fifo", "sjf"):
        res, done = serve_once(model, params, trace, policy=policy,
                               slots=slots, steps=min(steps_mix),
                               guidance=guidance_mix[0], lockstep=False,
                               max_steps=max_steps, sched_policy=sched)
        res["by_steps"] = summarize_by_steps(done)
        report["runs"].append(res)
    # headline: SJF must not lose on the short jobs' p95 (that's its
    # point).  A small/unlucky trace may never draw the short budget, so
    # the headline is None rather than a KeyError in that case.
    short = str(min(steps_mix))
    runs = {r["sched_policy"]: r for r in report["runs"]}
    for sched in ("fifo", "sjf"):
        grp = runs[sched]["by_steps"].get(short)
        report[f"short_job_p95_{sched}"] = (
            grp["latency_steps_p95"] if grp else None)
    return report


def run() -> List[dict]:
    """benchmarks/run.py driver entry: compact CSV rows."""
    report = benchmark()
    rows = []
    for r in report["runs"]:
        budgets = " ".join(
            f"steps{n}:p95={v['latency_steps_p95']:.0f}"
            f",cache={v['cache_ratio']:.3f}"
            for n, v in r["by_steps"].items())
        rows.append({
            "name": (f"serving_hetero/{report['config']['dit']}"
                     f"/{r['policy']}/{r['sched_policy']}"),
            "us_per_call": r["model_step_ms"] * 1e3,
            "derived": (f"p95_latency_steps={r['latency_steps_p95']:.0f}"
                        f" p50={r['latency_steps_p50']:.0f} {budgets}"),
        })
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dit", default="dit-b2")
    ap.add_argument("--policy", default="fastcache")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--steps-mix", default="4,8",
                    help="comma list of per-request DDIM step budgets "
                         "(paper-scale: 20,50)")
    ap.add_argument("--guidance-mix", default="1.0,4.0")
    ap.add_argument("--rate", type=float, default=0.25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="",
                    help="write the JSON report here (default: stdout)")
    args = ap.parse_args()
    report = benchmark(
        dit=args.dit, policy=args.policy, requests=args.requests,
        slots=args.slots,
        steps_mix=[int(v) for v in args.steps_mix.split(",") if v],
        guidance_mix=[float(v) for v in args.guidance_mix.split(",") if v],
        rate=args.rate, seed=args.seed)
    text = json.dumps(report, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        print(f"[serving_hetero] report written to {args.json}")
    else:
        print(text)


if __name__ == "__main__":
    main()
