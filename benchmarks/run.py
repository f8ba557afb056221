"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table1,roofline]
    PYTHONPATH=src python -m benchmarks.run --suite serving \
        --bench-out BENCH_serving.json

Prints ``name,us_per_call,derived`` CSV rows (stdout) — reduced-scale CPU
measurements for the paper's tables plus the roofline report derived from the
production-mesh dry-run artifacts (experiments/dryrun/).  With
``--bench-out``, suites that expose a ``write_trajectory`` hook (currently
``serving``) instead append one perf-trajectory entry — per-policy p50/p95
latency, steps/sec, cache ratio, and the metrics-plane overhead — to the
committed BENCH_*.json so speedups are machine-read across PRs.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "table1": ("benchmarks.table1_policies", "Table 1/12: policy comparison"),
    "table2": ("benchmarks.table2_ablation", "Table 2/9: STR/SC/MB ablation"),
    "table5": ("benchmarks.table5_static_ratio",
               "Table 5/Fig 1: static-ratio under motion"),
    "table6": ("benchmarks.table6_thresholds",
               "Table 6/Fig 3: threshold robustness"),
    "tokens": ("benchmarks.table_tokens",
               "Token compression on the serving path: keep-ratio + Table "
               "15 kNN-K sweep (latency, audit error, latent FID-proxy)"),
    "decode_gate": ("benchmarks.decode_gate",
                    "Beyond-paper: AR-decode statistical gate"),
    "batched_gate": ("benchmarks.batched_gate",
                     "Per-sample vs global gating on heterogeneous batches"),
    "serving": ("benchmarks.serving_diffusion",
                "Continuous vs lockstep diffusion serving under Poisson "
                "arrivals"),
    "serving_sharded": ("benchmarks.serving_sharded",
                        "Sharded vs single-device diffusion serving across "
                        "(data, model) mesh topologies (8-virtual-device "
                        "CPU subprocess)"),
    "serving_overload": ("benchmarks.serving_overload",
                         "SLO control plane under a bursty overload trace: "
                         "goodput vs p99 latency per cache-ratio shedding "
                         "level, audit-measured quality cost"),
    "serving_hetero": ("benchmarks.serving_hetero",
                       "Heterogeneous sampling plans (mixed step budgets/"
                       "guidance) under Poisson arrivals: FIFO vs SJF, "
                       "cache ratio by step budget"),
    "kernels": ("benchmarks.kernels_bench", "Kernel microbenchmarks"),
    "roofline": ("benchmarks.roofline", "Roofline from dry-run artifacts"),
}


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", "--suite", dest="only", default="",
                    help="comma-separated suite names (default: all)")
    ap.add_argument("--bench-out", default="",
                    help="append a perf-trajectory entry (suites exposing "
                         "write_trajectory, e.g. serving -> "
                         "BENCH_serving.json) instead of timing CSV rows")
    args = ap.parse_args()
    picked = [s.strip() for s in args.only.split(",") if s.strip()] \
        or list(SUITES)

    failures = 0
    if args.bench_out:
        # trajectory mode: the picked suites write/append the committed
        # BENCH_*.json point instead of printing CSV timing rows
        for name in picked:
            mod_name, desc = SUITES[name]
            print(f"# {name}: {desc}", file=sys.stderr, flush=True)
            try:
                mod = __import__(mod_name, fromlist=["write_trajectory"])
                if not hasattr(mod, "write_trajectory"):
                    raise AttributeError(
                        f"suite {name!r} has no trajectory writer")
                doc = mod.write_trajectory(args.bench_out)
                entry = doc["entries"][-1]
                extra = ""
                if "metrics_overhead_pct" in entry:
                    extra += (f", metrics overhead "
                              f"{entry['metrics_overhead_pct']:+.2f}%")
                if "audit_overhead_pct" in entry:
                    extra += (f", audit overhead "
                              f"{entry['audit_overhead_pct']:+.2f}%")
                if "goodput_monotone" in entry:
                    extra += (f", goodput monotone="
                              f"{entry['goodput_monotone']}, quality "
                              f"cost monotone="
                              f"{entry['quality_cost_monotone']}")
                print(f"{name}: wrote trajectory entry "
                      f"({len(entry['points'])} points{extra}) "
                      f"-> {args.bench_out}", flush=True)
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"{name}: ERROR: {type(e).__name__}: {e}",
                      flush=True)
                traceback.print_exc(file=sys.stderr)
        if failures:
            raise SystemExit(1)
        return

    print("name,us_per_call,derived")
    for name in picked:
        mod_name, desc = SUITES[name]
        print(f"# {name}: {desc}", file=sys.stderr, flush=True)
        try:
            mod = __import__(mod_name, fromlist=["run"])
            for row in mod.run():
                print(f"{row['name']},{row['us_per_call']:.1f},"
                      f"\"{row['derived']}\"", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},0,\"ERROR: {type(e).__name__}: {e}\"", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
