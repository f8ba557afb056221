"""Find a cell's knee and its batch once, on the chip: serve the cell's
traffic at a list of slot counts and, for an open-loop mix, at a list of
rates, each for a window, in one process.

    python3 bench/sweep.py --workload <name> --rates 3,4,5,6 --seconds 20
    python3 bench/sweep.py --workload <name> --slots 4,8,16,32 --seconds 20

A rate is sustained when the queue does not grow over the window: no more
than ``slots`` requests per engine wait at the close, and the second half
of the window's requests wait no longer than the first half's plus one
service time; and when queueing stays out of the median: the median
latency is within 1.5 service times.  The knee is the highest sustained
rate; the cell's mix is then set to about four fifths of it.  A backlog mix is
served once per slot count.  Writes ``chiprun_out/sweep_<name>.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def window_row(cfg, w, reqs, seconds: float, dev) -> dict:
    import numpy as np
    done = [r for r in reqs if r.done_t is not None]
    lat = np.array([(r.done_t if r.done_t is not None else w.drained_s)
                    - r.due for r in reqs])
    wait = np.array([r.admit_t - r.due for r in reqs])
    service = float(np.median(lat - wait))
    return {
        "slots": int(cfg["slots"]), "requests": len(reqs),
        "completed_per_s": sum(1 for r in done if r.done_t <= seconds)
        / seconds,
        "latency_p50_ms": 1e3 * float(np.percentile(lat, 50)),
        "latency_p95_ms": 1e3 * float(np.percentile(lat, 95)),
        "wait_first_half_ms": 1e3 * float(np.mean(wait[:len(reqs) // 2])),
        "wait_second_half_ms": 1e3 * float(np.mean(wait[len(reqs) // 2:])),
        "queued_at_close": sum(1 for r in reqs if r.admit_t > w.close_s),
        "serve_step_ms": 1e3 * w.busy_s / max(w.model_steps, 1),
        "busy_share": w.busy_s / w.close_s,
        "service_ms": 1e3 * service,
        "peak_bytes_in_use": (dev.memory_stats() or {}).get(
            "peak_bytes_in_use"),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--slots", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench import loadgen
    from bench.run import serving, set_up
    from bench.spec import load_cell
    from bench.window import drive

    cell = load_cell(args.workload)
    su = set_up(cell)
    mix = cell.mix
    params = su.family.make_params(su.dims, args.seed, cell.config["dtype"])
    rows = []
    for slots in [int(s) for s in args.slots.split(",") if s] or [
            int(cell.config["slots"])]:
        cfg = dict(cell.config, slots=slots)
        srv = serving(cfg, params, su.max_steps, root=cell.root)
        if mix["arrival"] == "backlog":
            stream, depth = loadgen.traffic(mix, args.seed, args.seconds,
                                            su.conds, slots)
            w = drive(srv, stream, args.seconds, su.counter,
                      backlog_depth=depth)
            rows.append(window_row(cfg, w, w.requests, args.seconds,
                                   su.dev))
            print(json.dumps(rows[-1]), flush=True)
        for rate in (float(r) for r in args.rates.split(",") if r):
            m = dict(mix, rate=rate)
            m.pop("segments", None)
            reqs = loadgen.poisson(m, args.seed, args.seconds, su.conds)
            srv.reset_clock()
            w = drive(srv, reqs, args.seconds, su.counter)
            row = dict(window_row(cfg, w, reqs, args.seconds, su.dev),
                       rate=rate)
            if w.replicas:
                row["replicas"] = w.replicas
            row["sustained"] = bool(
                row["queued_at_close"] <= slots * len(srv.engines)
                and row["wait_second_half_ms"]
                <= row["wait_first_half_ms"] + row["service_ms"]
                and row["latency_p50_ms"] <= 1.5 * row["service_ms"])
            rows.append(row)
            print(json.dumps(row), flush=True)
        del srv
    knee = {}                   # slots -> highest rate, all below sustained
    for r in sorted((r for r in rows if "rate" in r),
                    key=lambda r: (r["slots"], r["rate"])):
        if r["slots"] not in knee or knee[r["slots"]][1]:
            ok = r["sustained"]
            knee[r["slots"]] = (r["rate"] if ok else
                                knee.get(r["slots"], (None,))[0], ok)
    knee = {s: k for s, (k, _) in knee.items()}
    out = {"workload": cell.name, "device": su.dev.device_kind,
           "seconds": args.seconds, "rows": rows, "knee": knee}
    dest = ROOT / "chiprun_out" / f"sweep_{cell.name}.json"
    dest.parent.mkdir(exist_ok=True)
    dest.write_text(json.dumps(out, indent=1))
    print(json.dumps({"knee": out["knee"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
