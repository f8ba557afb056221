"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: the device must be a TPU that ``bench/peaks.py`` knows, with as
many chips as the cell asks for, or the run exits non-zero and prints no
result; JAX's persistent compilation cache is turned on; the weights are
made on the device from ``--seed`` by the configuration's model family
(``bench/spec.py``); every program the window runs is run once, on every
replica (set-up ends here, at the first due request); the window serves
the cell's traffic for ``--seconds``; the outputs are compared with the
family's plain reference (``bench/check.py``); the last line of stdout is
one JSON object.
With ``--trace 1`` the profiler traces the window's last few seconds
(stopping it stalls the host, so it stops after the drain) and the
per-layer metrics are reported instead of the end-to-end ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import NamedTuple, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
TRACE_S = 4.0          # traced seconds, at the end of the window


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, require_chip: bool):
    import jax
    from bench.peaks import peak_of
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from None
    dev = devs[0]
    peak = None
    if require_chip:
        if dev.platform != "tpu":
            raise NoChip(f"no TPU: JAX's first device is {dev.platform!r} "
                         f"({dev.device_kind})")
        try:
            peak = peak_of(dev.device_kind)
        except KeyError as e:
            raise NoChip(str(e)) from None
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return dev, devs[:chips], peak


def compile_cache() -> str:
    """JAX's persistent compilation cache (``repro.launch.compile_cache``),
    keeping every program however quickly it compiled, so that a cell's
    second run in a checkout compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class SetUp(NamedTuple):
    dev: object
    devs: list
    peak: object           # peaks.Peak; None off the chip
    counter: object        # window.CompileCounter
    cache_dir: Optional[str]
    family: object         # the module bench/families/<family>.py
    dims: object           # the family's sizes
    algo: object           # the family's algorithm settings
    max_steps: int
    conds: object          # what loadgen draws each request's cond from


def set_up(cell, require_chip: bool = True) -> SetUp:
    """The device check, the compile cache, the model family and the sizes
    of ``cell``: what every entry point (this one, ``calibrate.py``,
    ``sweep.py``) does before it makes weights."""
    from bench import loadgen
    from bench.spec import family
    from bench.window import CompileCounter
    dev, devs, peak = device_info(cell.chips, require_chip)
    cache_dir = compile_cache() if require_chip else None
    fam = family(cell.config, cell.root)
    dims = fam.dims_of(cell.config)
    return SetUp(dev, devs, peak, CompileCounter(), cache_dir, fam, dims,
                 fam.algo_of(cell.config), loadgen.max_steps(cell.mix),
                 fam.conds(cell.config, dims))


def serving(cfg, params, max_steps: int, engine_hook=None, root=ROOT):
    """What configuration ``cfg`` deploys (``window.Server``), every program
    it runs in the window run once."""
    from bench.spec import family
    from bench.window import deploy
    return deploy(family(cfg, root), cfg, params, max_steps, engine_hook)


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True, engine_hook=None):
    """One run of ``cell``; returns the result line as a dict."""
    import numpy as np

    from bench import check, loadgen
    from bench.results import RunData
    from bench.spec import metric_reader
    from bench.window import drive

    su = set_up(cell, require_chip)
    cfg, mix, fam, d, algo = (cell.config, cell.mix, su.family, su.dims,
                              su.algo)
    slots = int(cfg["slots"])
    params = fam.make_params(d, seed, cfg["dtype"])
    srv = serving(cfg, params, su.max_steps, engine_hook, cell.root)
    traffic, depth = loadgen.traffic(mix, seed, seconds, su.conds, slots)
    watch = check.plan(
        loadgen.candidates(mix, seed, seconds, su.conds, slots),
        int(cfg["check"]["sample"]), seed)

    annotate, on_tick, tracer = None, None, None
    if trace:
        from bench import trace_reduce
        tracer = trace_reduce.Recorder(TRACE_DIR,
                                       max(0.0, seconds - TRACE_S))
        annotate, on_tick = tracer.annotate, tracer.tick
    srv.block()
    setup_s = time.perf_counter() - t_start
    counter = su.counter
    log(f"set-up {setup_s:.1f}s ({counter.compiles} compiles, "
        f"{counter.compile_s:.1f}s; {counter.cache_hits} cache hits; "
        f"cache {su.cache_dir})")
    w = drive(srv, traffic, seconds, counter, backlog_depth=depth,
              annotate=annotate, on_tick=on_tick, watch=watch)
    mem_peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for x in su.devs) or None
    summary = tracer.finish() if tracer is not None else None
    log("longest loop turns: " + ", ".join(
        f"{1e3 * dt:.1f} ms at {at:.2f} s ({what})"
        for dt, at, what in w.stalls))

    run = RunData(cell=cell, dims=d, shape=fam.shape_of(d, algo),
                  algo=algo, window=w, setup_s=setup_s, peak=su.peak,
                  memory_peak_bytes=mem_peak, trace=summary, family=fam)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the check: the window's outputs against the plain reference, once
    # the program's state is freed
    sample = check.sampled(w.requests, watch)
    del srv
    p32 = fam.to_f32(params)
    del params
    t0 = time.perf_counter()
    ref = fam.reference_outputs(p32, d, algo, sample)
    values = {k: math.inf for k in cfg["check"]["limits"]}
    if sample:
        values = fam.gaps(d, sample, check.served_outputs(sample), ref)
    breaks, unread = fam.rule_breaks(sample, algo)
    non_finite = sum(1 for r in w.requests if r.latents is not None
                     and not np.isfinite(r.latents).all())
    unfinished = sum(1 for r in w.requests if r.latents is None)
    values.update(cache_rule_breaks=breaks, unfinished=unfinished,
                  non_finite=non_finite, compiles_in_window=w.compiles)
    lim = dict(cfg["check"]["limits"], cache_rule_breaks=0, unfinished=0,
               non_finite=0, compiles_in_window=0)
    for name in [k for k, v in lim.items() if v is None]:
        # a number the configuration states no limit for (PERF.md says why)
        log(f"{name} = {values.pop(name)!r} (shown, not compared)")
        del lim[name]
    correct, shown = check.verdict(values, lim)
    log(f"window {w.seconds:.0f}s closed at {w.close_s:.2f}s, drained at "
        f"{w.drained_s:.2f}s: {len(w.requests)} attempted, "
        f"{w.model_steps} serve steps, {len(sample)} compared "
        f"({sum(len(check.gated_steps(r)) for r in sample)} gated steps, "
        f"{unread} rows whose decisions the state does not show) in "
        f"{time.perf_counter() - t0:.1f}s")
    if w.replicas:
        log("requests admitted by replica: "
            + ", ".join(str(n) for n in w.replicas) + "; compared: "
            + ", ".join(str(sum(r.replica == i for r in sample))
                        for i in range(len(w.replicas))))
    for name, v in shown.items():
        log(f"check {name} = {v['value']!r} (limit {v['limit']!r})")

    device = {"platform": su.dev.platform, "kind": su.dev.device_kind,
              "count": len(su.devs), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(correct), "attempted": len(w.requests),
           "failed": unfinished + non_finite, "metrics": metrics,
           "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["breakdown"] = summary.breakdown()
    out["check"] = shown
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no src/repro beside bench/: run from a checkout of the "
            f"repository ({ROOT})")
        return 1
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.spec import load_cell
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START)
    except NoChip as e:
        log(str(e))
        return 1
    finally:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
