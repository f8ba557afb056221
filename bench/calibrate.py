"""Read the numbers that ``correct`` compares, for the program over many
seeds and for the float8 control over a few, at a cell's own size and
load, in one process (the limits in the configuration files were set
from these readings).

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 \
        --control-seeds 11,12,13 --seconds 10

Per seed: the cell's weights and traffic from that seed, a window of
``--seconds`` at the cell's load through the engines, the sample that a run
compares, and the model family's reference.  The control is the reference
in lower precision (``quant=True``; float8 for DiT) put in the program's
place, on the same sample and from the same inputs.
Writes ``chiprun_out/calibrate_<name>.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(cell, su, srv, seed: int, seconds: float, control: bool):
    """One seed's row: the program's numbers, and the control's."""
    from bench import check, loadgen
    from bench.window import drive
    cfg, mix, fam, d = cell.config, cell.mix, su.family, su.dims
    slots = int(cfg["slots"])
    params = fam.make_params(d, seed, cfg["dtype"])
    srv.place(params)             # same shapes: the step does not recompile
    srv.reset_clock()
    traffic, depth = loadgen.traffic(mix, seed, seconds, su.conds, slots)
    watch = check.plan(
        loadgen.candidates(mix, seed, seconds, su.conds, slots),
        int(cfg["check"]["sample"]), seed)
    w = drive(srv, traffic, seconds, su.counter, backlog_depth=depth,
              watch=watch)
    sample = check.sampled(w.requests, watch)
    p32 = fam.to_f32(params)
    ref = fam.reference_outputs(p32, d, su.algo, sample)
    breaks, unread = fam.rule_breaks(sample, su.algo)
    row = {"seed": seed, "compared": len(sample),
           "gated_steps": sum(len(check.gated_steps(r)) for r in sample),
           "unread_rows": unread,
           "program": dict(fam.gaps(d, sample, check.served_outputs(sample),
                                    ref), cache_rule_breaks=breaks)}
    if w.replicas:
        row["replicas"] = w.replicas
    if control:
        ctl = fam.reference_outputs(p32, d, su.algo, sample, quant=True)
        row["control"] = fam.gaps(d, sample, ctl, ref)
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.run import serving, set_up
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    su = set_up(cell)
    cfg = cell.config
    control = {int(s) for s in args.control_seeds.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",")]
    srv = serving(cfg, su.family.make_params(su.dims, seeds[0], cfg["dtype"]),
                  su.max_steps, root=cell.root)
    rows = []
    for seed in seeds:
        rows.append(readings(cell, su, srv, seed, args.seconds,
                             seed in control))
        print(json.dumps(rows[-1]), flush=True)
    out = {"workload": cell.name, "device": su.dev.device_kind,
           "seconds": args.seconds, "rows": rows}
    for who in ("program", "control"):
        got = [r[who] for r in rows if who in r]
        if got:
            out[who] = {k: {"min": min(g[k] for g in got),
                            "max": max(g[k] for g in got)}
                        for k in got[0]}
    dest = ROOT / "chiprun_out" / f"calibrate_{cell.name}.json"
    dest.parent.mkdir(exist_ok=True)
    dest.write_text(json.dumps(out, indent=1))
    print(json.dumps({k: out.get(k) for k in ("program", "control")}))
    srv.block()
    return 0


if __name__ == "__main__":
    sys.exit(main())
