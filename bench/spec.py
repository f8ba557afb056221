"""Finds what a cell needs by name, from ``BENCHMARK.json`` and the files
beside it, so that a cell, a configuration, a traffic mix or a metric is
added by adding files and entries and never by editing one:

    bench/configs/<config>.json   named by the ``configs`` entry's ``file``
    bench/mixes/<traffic>.json    one traffic mix, read by ``bench/loadgen.py``
    bench/metrics/<metric>.py     one reader per metric; a metric split by
                                  cell kind (``mfu.poisson``) falls back to
                                  the reader of its base name (``mfu.py``)
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple

ROOT = Path(__file__).resolve().parents[1]


class Cell(NamedTuple):
    name: str
    chips: int
    config: Dict           # the configuration file, with its name
    mix: Dict              # the traffic mix file, with its name
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path = ROOT      # where the cell's files were found


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: Dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = {"name": conf["name"], **json.load(f)}
    with open(root / "bench" / "mixes" / f"{w['traffic']}.json") as f:
        mix = {"name": w["traffic"], **json.load(f)}
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, [])]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer, root)


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``, else of the file
    named by the part of ``name`` before its first dot."""
    for stem in (name, name.split(".", 1)[0]):
        path = root / "bench" / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"bench.metrics.{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader bench/metrics/{name}.py")
