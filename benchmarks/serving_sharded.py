"""Sharded-serving benchmark driver row: the topology sweep of
``benchmarks/serving_diffusion.py --mesh``, folded into compact CSV rows —
one row per (data, model) topology with p50/p95 latency, steps/sec and
parity against the single-device engine.

On a TPU host the sweep runs in this process on the real chips (topologies
wider than the host are reported as skipped): a chip belongs to one
process, so a child could not reach it.  Elsewhere it runs on an
8-virtual-device CPU mesh in a subprocess — the parent benchmark process
has already initialized jax on a single CPU device, and XLA only honors
``--xla_force_host_platform_device_count`` at first init.

    PYTHONPATH=src python -m benchmarks.run --only serving_sharded
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from typing import List

from repro.launch.compile_cache import enable_compile_cache

TOPOLOGIES = "1x1,4x1,8x1,4x2"
DEVICES = 8


def run(*, topologies: str = TOPOLOGIES, requests: int = 8, slots: int = 4,
        steps: int = 6, policy: str = "fastcache", rate: float = 0.25,
        seed: int = 0) -> List[dict]:
    import jax
    if jax.default_backend() == "tpu":
        from benchmarks.serving_diffusion import (benchmark_topologies,
                                                  parse_topologies)
        report = benchmark_topologies(
            topologies=parse_topologies(topologies), policies=(policy,),
            requests=requests, slots=slots, steps=steps, rate=rate,
            seed=seed)
    else:
        report = _cpu_subprocess_sweep(topologies=topologies,
                                       requests=requests, slots=slots,
                                       steps=steps, policy=policy, rate=rate,
                                       seed=seed)
    return _rows(report, policy)


def _cpu_subprocess_sweep(*, topologies: str, requests: int, slots: int,
                          steps: int, policy: str, rate: float,
                          seed: int) -> dict:
    env = dict(os.environ)
    # virtual devices exist only on the CPU backend; never let the child
    # reach for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    kept = [t for t in env.get("XLA_FLAGS", "").split()
            if not t.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={DEVICES}"])
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as f:
        out_path = f.name
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.serving_diffusion",
             "--mesh", topologies, "--policies", policy,
             "--requests", str(requests), "--slots", str(slots),
             "--steps", str(steps), "--rate", str(rate),
             "--seed", str(seed), "--json", out_path],
            env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            # surface the child's traceback — a bare CalledProcessError
            # makes CI failures undebuggable
            sys.stderr.write(proc.stderr)
            raise RuntimeError(
                f"serving_diffusion sweep subprocess failed "
                f"(exit {proc.returncode}); stderr above")
        with open(out_path) as f:
            return json.load(f)
    finally:
        os.unlink(out_path)


def _rows(report: dict, policy: str) -> List[dict]:
    rows = []
    for r in report["topologies"]:
        topo = r["topology"]
        name = (f"serving_sharded/{report['config']['dit']}"
                f"/{r.get('policy', policy)}"
                f"/data{topo['data']}xmodel{topo['model']}")
        if r.get("skipped"):
            rows.append({"name": name, "us_per_call": 0.0,
                         "derived": f"SKIPPED: {r['skipped']}"})
            continue
        # parity fields exist only when the (1,1) baseline ran in the sweep
        parity = ""
        if "max_abs_diff_vs_single" in r:
            parity = (f" sched_parity="
                      f"{r['schedule_identical_vs_single']}"
                      f" max_abs_diff_vs_single="
                      f"{r['max_abs_diff_vs_single']:.1e}")
        rows.append({
            "name": name,
            "us_per_call": r["model_step_ms"] * 1e3,
            "derived": (f"steps_per_s={r['steps_per_s']:.2f}"
                        f" p95_latency_steps={r['latency_steps_p95']:.0f}"
                        f" p50={r['latency_steps_p50']:.0f}" + parity +
                        f" cache_ratio="
                        f"{r['cache']['block_cache_ratio']:.3f}"),
        })
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for row in run():
        print(f"{row['name']},{row['us_per_call']:.1f},\"{row['derived']}\"")
