"""CI perf-regression gate against the committed serving trajectory.

    PYTHONPATH=src python -m benchmarks.bench_check [--bench BENCH_serving.json]

The committed BENCH file holds trajectory entries from one or more suites
(``serving`` — per-policy continuous-serving points; ``serving_overload``
— per-shedding-level SLO control-plane points; entries written before
suites shared the file carry no tag and count as ``serving``).  For each
suite present, this gate measures a FRESH trajectory point (same
benchmark config as that suite's latest committed entry, same
policies/levels) and fails — exit 1 with a per-point table — if any
point's ``model_step_ms`` regressed more than ``--max-regress-pct``
(default 25%) against the committed number.  Only slowdowns gate;
speedups and new points pass.

The 25% default is deliberately loose: these are short reduced-scale CPU
runs on shared CI machines, so the gate is meant to catch "the serve step
got 2x slower" structural regressions, not 5% noise.  A legitimate
slowdown (e.g. a PR that knowingly trades step time for quality) is
ridden past the gate by setting ``BENCH_CHECK_OVERRIDE=<reason>`` in the
environment — CI wires that to a ``perf-regression-ok`` PR label — which
downgrades failures to warnings.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

from repro.launch.compile_cache import enable_compile_cache

OVERRIDE_ENV = "BENCH_CHECK_OVERRIDE"

# suite tag -> module exposing fresh_for_check(baseline_entry) -> entry
SUITE_MODULES = {
    "serving": "benchmarks.serving_diffusion",
    "serving_overload": "benchmarks.serving_overload",
}


def check_regression(baseline_entry: Dict, fresh_entry: Dict,
                     max_regress_pct: float = 25.0) -> List[Dict]:
    """Compare two trajectory entries point-by-point (keyed on
    ``policy`` — for the overload suite that is ``<policy>@<level>``);
    return one record per point whose fresh ``model_step_ms`` exceeds
    the baseline's by more than ``max_regress_pct`` percent.  Points
    present only on one side are skipped (renames/additions must not
    gate), as are baseline points with non-positive step time
    (corrupt/placeholder data)."""
    base = {p["policy"]: p for p in baseline_entry.get("points", [])}
    fresh = {p["policy"]: p for p in fresh_entry.get("points", [])}
    failures = []
    for policy in base:
        if policy not in fresh:
            continue
        b = float(base[policy].get("model_step_ms", 0.0))
        f = float(fresh[policy].get("model_step_ms", 0.0))
        if b <= 0.0:
            continue
        pct = (f - b) / b * 100.0
        if pct > max_regress_pct:
            failures.append({"policy": policy, "baseline_ms": b,
                             "fresh_ms": f, "regress_pct": pct})
    return failures


def _check_suite(suite: str, baseline: Dict,
                 max_regress_pct: float) -> List[Dict]:
    """Measure a fresh point for one suite and report its table; returns
    the regression records (empty = pass)."""
    mod_name = SUITE_MODULES.get(suite)
    if mod_name is None:
        print(f"[bench-check] {suite}: unknown suite tag; skipping "
              "(no gate)")
        return []
    points = baseline.get("points", [])
    if not points:
        print(f"[bench-check] {suite}: baseline entry has no points "
              "(pass)")
        return []
    print(f"[bench-check] {suite}: baseline {baseline.get('date', '?')} "
          f"({len(points)} points); measuring fresh point ...",
          flush=True)
    mod = __import__(mod_name, fromlist=["fresh_for_check"])
    fresh = mod.fresh_for_check(baseline)
    failures = check_regression(baseline, fresh, max_regress_pct)
    for p in fresh["points"]:
        base = next((b for b in points if b["policy"] == p["policy"]),
                    None)
        tag = ""
        if base and float(base.get("model_step_ms", 0.0)) > 0.0:
            pct = ((p["model_step_ms"] - base["model_step_ms"])
                   / base["model_step_ms"] * 100.0)
            tag = f" ({pct:+.1f}% vs baseline)"
        print(f"[bench-check]   {p['policy']}: "
              f"{p['model_step_ms']:.3f} ms/step{tag}")
    return failures


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default="BENCH_serving.json",
                    help="committed trajectory file to gate against")
    ap.add_argument("--max-regress-pct", type=float, default=25.0)
    ap.add_argument("--suite", default="",
                    help="comma list of suite tags to gate (default: "
                         "every suite present in the BENCH file)")
    args = ap.parse_args()
    try:
        with open(args.bench) as f:
            doc = json.load(f)
        entries = doc["entries"]
        if not entries:
            raise KeyError("entries")
    except (OSError, ValueError, KeyError):
        print(f"[bench-check] no usable baseline in {args.bench}; "
              "nothing to gate against (pass)")
        return
    # latest entry per suite is that suite's baseline (entries are
    # appended in date order; untagged legacy entries are 'serving')
    by_suite: Dict[str, Dict] = {}
    for e in entries:
        by_suite[e.get("suite", "serving")] = e
    picked = [s.strip() for s in args.suite.split(",") if s.strip()] \
        or sorted(by_suite)
    failures: List[Dict] = []
    for suite in picked:
        if suite not in by_suite:
            print(f"[bench-check] {suite}: no committed entry in "
                  f"{args.bench} (pass)")
            continue
        failures.extend(_check_suite(suite, by_suite[suite],
                                     args.max_regress_pct))
    if not failures:
        print(f"[bench-check] OK: no point regressed more than "
              f"{args.max_regress_pct:.0f}%")
        return
    override = os.environ.get(OVERRIDE_ENV, "")
    for f_ in failures:
        print(f"[bench-check] REGRESSION {f_['policy']}: "
              f"{f_['baseline_ms']:.3f} -> {f_['fresh_ms']:.3f} ms/step "
              f"({f_['regress_pct']:+.1f}% > "
              f"{args.max_regress_pct:.0f}%)", file=sys.stderr)
    if override:
        print(f"[bench-check] overridden ({OVERRIDE_ENV}={override!r}); "
              "treating regressions as warnings")
        return
    print(f"[bench-check] FAIL: set {OVERRIDE_ENV} (CI: the "
          "perf-regression-ok label) to override a known slowdown",
          file=sys.stderr)
    raise SystemExit(1)


if __name__ == "__main__":
    main()
