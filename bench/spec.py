"""Finds what a cell needs by name, from ``BENCHMARK.json`` and the files
beside it, so that a cell, a configuration, a model family, a traffic mix
or a metric is added by adding files and entries and never by editing one:

    bench/configs/<config>.json   named by the ``configs`` entry's ``file``
    bench/families/<family>.py    the model family that the configuration
                                  file names under ``"family"`` (no default)
    bench/mixes/<traffic>.json    one traffic mix, read by ``bench/loadgen.py``
    bench/metrics/<metric>.py     one reader per metric; a metric split by
                                  cell kind (``mfu.poisson``) falls back to
                                  the reader of its base name (``mfu.py``)

A configuration file may state ``"replicas": N``: the deployment is then N
engines, one on each of the first N devices, behind the program's
``ReplicaRouter`` (``bench/window.py``).  Without it there is one engine.

A family module is the only place that knows the model.  The harness
(``run.py``, ``window.py``, ``check.py``, ``loadgen.py``, ``calibrate.py``,
``sweep.py``, ``results.py`` and the metric readers) calls nothing of a
model but these functions of it:

    dims_of(cfg)                     the sizes, from the configuration file
    make_params(dims, seed, dtype)   the weights from the seed, on the
                                     default device, in one jitted call
    build(cfg, params, max_steps)    (engine, warm-up requests): the serving
                                     engine over ``params`` on the default
                                     device, and the two ``loadgen.Request``
                                     that ``window.warm_up`` admits (the
                                     first with one step more than the
                                     second) to run every program once
    conds(cfg, dims)                 k: each request's ``cond`` is drawn
                                     uniform over range(k) from the run's
                                     seed (``bench/loadgen.py``)
    to_engine(request, clock)        the program's request for a
                                     ``loadgen.Request`` due at engine step
                                     ``clock``, its ``cond`` made into the
                                     program's conditioning
    algo_of(cfg)                     the algorithm's settings, for the rest
    to_f32(params)                   the reference's copy of the weights
    reference_outputs(p32, dims, algo, sample, quant=False)
                                     rid -> {step: the plain reference's
                                     output}, for the steps copied from the
                                     window (``check.py``); ``quant=True``
                                     is the control in lower precision
    gaps(dims, sample, served, ref)  {name: widest gap}, compared with the
                                     configuration's ``check.limits``
    rule_breaks(sample, algo)        (decisions that break the algorithm's
                                     rules, rows whose decisions cannot be
                                     read), the first compared with 0
    shape_of(dims, algo)             the serving shapes, for the two below
    request_flops(shape, algo, request, counters, steps)
                                     FLOPs of a request's first ``steps``
                                     steps, from its counters (``mfu``)
    kernel_costs(shape, slots)       {kernel: flops.Cost} per call at an
                                     engine of ``slots`` slots (rooflines)
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, NamedTuple

ROOT = Path(__file__).resolve().parents[1]
_MODULES: Dict[Path, ModuleType] = {}


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


def _module(path: Path, name: str) -> ModuleType:
    """The module of the file at ``path``, loaded once per process."""
    path = path.resolve()
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def family(cfg: Dict, root: Path = ROOT) -> ModuleType:
    """The module ``bench/families/<cfg["family"]>.py``."""
    if "family" not in cfg:
        raise KeyError(f"configuration {cfg.get('name')!r} states no "
                       f"\"family\"")
    name = cfg["family"]
    path = root / "bench" / "families" / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no model family bench/families/{name}.py")
    return _module(path, f"bench.families.{name}")


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(run)`` of ``bench/metrics/<name>.py``, else of the file
    named by the part of ``name`` before its first dot."""
    for stem in (name, name.split(".", 1)[0]):
        path = root / "bench" / "metrics" / f"{stem}.py"
        if path.exists():
            return _module(path, f"bench.metrics.{stem.replace('.', '_')}"
                           ).read
    raise FileNotFoundError(f"no reader bench/metrics/{name}.py")
