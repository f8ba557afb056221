import os
# A CPU-only tool: it runs on virtual host devices and never takes a chip,
# even on a TPU host (where a second process on the chip fails or hangs).
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + (os.environ.get("REPRO_DRYRUN_DEVICES") or "512")
                           + " " + os.environ.get("XLA_FLAGS", ""))
# ^ MUST run before any jax import: jax locks the device count on first init.
#   REPRO_DRYRUN_DEVICES overrides for small-mesh CI tests.

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production mesh, print memory/cost analysis, extract collective bytes
from the partitioned HLO, and write one JSON artifact per combo.

CPU only: the production meshes are virtual host devices (XLA:CPU), so
the numbers are compile-time analyses of the CPU programs, never chip
measurements.  To compile for the TPU itself without a chip, use a
described topology (see tests/test_tpu_compile.py).

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-14b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the system.  Artifacts land in experiments/dryrun/ and feed
benchmarks/roofline.py (EXPERIMENTS.md §Dry-run / §Roofline).
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.configs.shapes import SHAPES
from repro.distributed.hlo import collective_bytes
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.specs import (Bundle, build_bundle, model_flops,
                                skip_reason)
from repro.models import flags as model_flags

# TPU v5e hardware constants (roofline denominators)
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link


def _measure_cost(arch: str, shape_name: str, mesh, num_layers: int,
                  prefix_groups: int, seq: int | None = None,
                  attn_seq_shard: bool = False) -> dict:
    """Compile a reduced-depth FULLY-UNROLLED variant and read exact
    per-device costs (XLA's HloCostAnalysis counts while bodies once, so the
    production scan-over-layers compile cannot give exact FLOPs; two of
    these extrapolate linearly in depth — see flags.UNROLL_INNER)."""
    with model_flags.unroll_inner():
        bundle = build_bundle(arch, shape_name, mesh,
                              prefix_groups=prefix_groups,
                              num_layers=num_layers, seq_override=seq,
                              attn_seq_shard=attn_seq_shard)
        jitted = jax.jit(bundle.step_fn, in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings)
        compiled = jitted.lower(*bundle.args).compile()
    cost = compiled.cost_analysis() or {}
    coll, _ = collective_bytes(compiled.as_text(), default_trip=1)
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "collectives": coll}


def _measure_at_depth(arch, shape_name, mesh, num_layers, prefix_groups,
                      target_seq: int | None, probe_seqs,
                      attn_seq_shard: bool = False) -> dict:
    """Cost at one depth. If `probe_seqs` is set, compile at those (small)
    sequence lengths and fit a quadratic in S per metric — every per-token
    cost in the system is at most quadratic in S (attention) and the probes
    sit on chunk-size multiples, so the polynomial is exact.  Used for
    ssm/hybrid archs whose unrolled inner scans make direct 32k compiles
    intractably slow."""
    if not probe_seqs:
        return _measure_cost(arch, shape_name, mesh, num_layers,
                             prefix_groups, attn_seq_shard=attn_seq_shard)
    import numpy as np
    probes = [_measure_cost(arch, shape_name, mesh, num_layers,
                            prefix_groups, seq=s,
                            attn_seq_shard=attn_seq_shard)
              for s in probe_seqs]
    xs = np.asarray(probe_seqs, dtype=float)

    def fit(ys):
        coeff = np.polyfit(xs, np.asarray(ys, dtype=float),
                           min(2, len(xs) - 1))
        return float(np.polyval(coeff, target_seq))

    kinds = set()
    for p in probes:
        kinds |= set(p["collectives"])
    return {
        "flops": fit([p["flops"] for p in probes]),
        "bytes": fit([p["bytes"] for p in probes]),
        "collectives": {k: max(0.0, fit([p["collectives"].get(k, 0.0)
                                         for p in probes])) for k in kinds},
    }


def _extrapolate(c1: dict, c2: dict, l1: int, l2: int, l: int) -> dict:
    def lin(a, b):
        return max(0.0, a + (b - a) * (l - l1) / (l2 - l1))

    kinds = set(c1["collectives"]) | set(c2["collectives"])
    return {
        "flops": lin(c1["flops"], c2["flops"]),
        "bytes": lin(c1["bytes"], c2["bytes"]),
        "collectives": {k: lin(c1["collectives"].get(k, 0.0),
                               c2["collectives"].get(k, 0.0))
                        for k in kinds},
    }


def _make_mesh(multi_pod: bool, mesh_shape: str = ""):
    if mesh_shape:
        dims = tuple(int(x) for x in mesh_shape.split(","))
        axes = ("pod", "data", "model") if len(dims) == 3 else ("data",
                                                                "model")
        return make_mesh(dims, axes)
    return make_production_mesh(multi_pod=multi_pod)


def run_one(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
            prefix_groups: int = 1, tag: str = "",
            mesh_shape: str = "", measure_cost: bool = True,
            attn_seq_shard: bool = False) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    if mesh_shape:
        mesh_name = f"mesh{mesh_shape.replace(',', 'x')}"
    reason = skip_reason(arch, shape_name)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "skip", "skip_reason": reason, "tag": tag}
    if reason:
        print(f"[dryrun] SKIP {arch} x {shape_name}: {reason}", flush=True)
        return rec
    try:
        mesh = _make_mesh(multi_pod, mesh_shape)
        n_chips = mesh.devices.size
        t0 = time.perf_counter()
        bundle: Bundle = build_bundle(arch, shape_name, mesh,
                                      prefix_groups=prefix_groups,
                                      attn_seq_shard=attn_seq_shard)
        jitted = jax.jit(bundle.step_fn, in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings)
        lowered = jitted.lower(*bundle.args)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

        mem = compiled.memory_analysis()
        mem_rec = {}
        if mem is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(mem, k, None)
                if v is not None:
                    mem_rec[k] = int(v)
        cost = compiled.cost_analysis() or {}
        flops = float(cost.get("flops", 0.0))
        bytes_accessed = float(cost.get("bytes accessed", 0.0))

        hlo = compiled.as_text()
        coll, diag = collective_bytes(hlo, default_trip=bundle.meta["n_super"])

        # ---- exact cost: reduced-depth unrolled variants, linear in depth;
        # ssm/hybrid additionally probe small sequence lengths and fit a
        # quadratic in S (their unrolled chunk loops make 32k compiles slow)
        cfg = get_config(arch)
        period = len(cfg.block_pattern) or 1
        shape = SHAPES[shape_name]
        t0 = time.perf_counter()
        if measure_cost:
            probe_seqs = None
            if (cfg.family in ("ssm", "hybrid")
                    and shape.kind in ("train", "prefill")):
                s = shape.seq_len
                if cfg.family == "ssm":
                    # attention-free: cost is exactly linear in S
                    probe_seqs = [min(512, s), min(1024, s)]
                else:
                    probe_seqs = [min(1024, s), min(2048, s), min(3072, s)]
                if len(set(probe_seqs)) < len(probe_seqs):
                    probe_seqs = None
            c1 = _measure_at_depth(arch, shape_name, mesh, period,
                                   prefix_groups, shape.seq_len, probe_seqs,
                                   attn_seq_shard=attn_seq_shard)
            c2 = _measure_at_depth(arch, shape_name, mesh, 2 * period,
                                   prefix_groups, shape.seq_len, probe_seqs,
                                   attn_seq_shard=attn_seq_shard)
            exact = _extrapolate(c1, c2, period, 2 * period, cfg.num_layers)
        else:
            # compile-proof only (multi-pod pass): reuse raw scan costs
            exact = {"flops": flops, "bytes": bytes_accessed,
                     "collectives": coll}
        t_cost = time.perf_counter() - t0

        mflops = model_flops(cfg, SHAPES[shape_name])
        # all cost numbers are for the per-device (partitioned) program
        terms = {
            "compute_s": exact["flops"] / PEAK_FLOPS,
            "memory_s": exact["bytes"] / HBM_BW,
            "collective_s": exact["collectives"].get("total", 0.0) / ICI_BW,
        }
        terms["dominant"] = max(
            (k for k in terms if k.endswith("_s")), key=lambda k: terms[k])
        rec.update({
            "status": "ok",
            "n_chips": n_chips,
            "params": bundle.meta["params"],
            "meta": bundle.meta,
            "per_device_flops": exact["flops"],
            "per_device_bytes_accessed": exact["bytes"],
            "collective_bytes": exact["collectives"],
            "scan_compile": {"flops": flops, "bytes": bytes_accessed,
                             "collectives": coll,
                             "collectives_static": diag["static"]},
            "memory_analysis": mem_rec,
            "model_flops_global": mflops,
            "model_flops_per_device": mflops / n_chips,
            "useful_flops_ratio": ((mflops / n_chips) / exact["flops"]
                                   if exact["flops"] else 0.0),
            "roofline": terms,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "cost_measure_s": round(t_cost, 2),
            "hlo_bytes": len(hlo),
        })
        print(f"[dryrun] OK {arch} x {shape_name} x {mesh_name}"
              f" flops/dev={exact['flops']:.3e}"
              f" bytes/dev={exact['bytes']:.3e}"
              f" coll/dev={exact['collectives'].get('total', 0):.3e}B"
              f" useful={rec['useful_flops_ratio']:.2f}"
              f" temp={mem_rec.get('temp_size_in_bytes', -1)/2**30:.2f}GiB"
              f" compile={t_compile:.1f}s cost={t_cost:.1f}s", flush=True)
        if mem is not None:
            print(f"         memory_analysis: {mem_rec}", flush=True)
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec.update({"status": "fail", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
        print(f"[dryrun] FAIL {arch} x {shape_name} x {mesh_name}: {e}",
              flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"_{tag}" if tag else ""
        path = os.path.join(out_dir,
                            f"{arch}_{shape_name}_{mesh_name}{suffix}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--prefix-groups", type=int, default=1)
    ap.add_argument("--tag", default="")
    ap.add_argument("--mesh-shape", default="",
                    help="override mesh, e.g. '2,2' (CI small-mesh tests)")
    ap.add_argument("--no-cost", action="store_true",
                    help="skip the cost-extrapolation compiles (multi-pod "
                         "compile-proof runs)")
    ap.add_argument("--moe-gather-decode", action="store_true",
                    help="perf variant: gather-based MoE for decode shapes")
    ap.add_argument("--attn-seq-shard", action="store_true",
                    help="perf variant: shard attention q/logits seq over "
                         "`model`")
    ap.add_argument("--attn-chunk", type=int, default=0,
                    help="perf variant: force chunked attention above this "
                         "Sq*Skv (elements)")
    ap.add_argument("--moe-constrain-dispatch", action="store_true",
                    help="perf variant: shard MoE dispatch intermediates")
    ap.add_argument("--ce-remat", action="store_true",
                    help="perf variant: rematerialize chunked-CE logits")
    args = ap.parse_args()
    if args.ce_remat:
        model_flags.CE_REMAT = True
    if args.attn_chunk:
        model_flags.DIRECT_MAX_ELEMS = args.attn_chunk
    if args.moe_constrain_dispatch:
        model_flags.MOE_CONSTRAIN_DISPATCH = True
    if args.moe_gather_decode:
        model_flags.MOE_GATHER_DECODE = True

    archs = list(ASSIGNED_ARCHS) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                results.append(run_one(arch, shape, mp, args.out,
                                       prefix_groups=args.prefix_groups,
                                       tag=args.tag,
                                       mesh_shape=args.mesh_shape,
                                       measure_cost=not args.no_cost,
                                       attn_seq_shard=args.attn_seq_shard))
    ok = sum(r["status"] == "ok" for r in results)
    skip = sum(r["status"] == "skip" for r in results)
    fail = sum(r["status"] == "fail" for r in results)
    print(f"[dryrun] done: {ok} ok, {skip} skip, {fail} fail", flush=True)
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
