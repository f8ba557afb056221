"""A copy of the benchmark's files with more cells, added only as new files
and new entries: a tiny configuration, a mix and a metric reader
(``tiny-short``); a model family that only the copy has, with a
configuration of its own (``tiny-own``); and the tiny configuration as two
replicas behind the router (``tiny-r2``, two devices).  The tests drive
them on the CPU."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SUBDIRS = ("configs", "families", "mixes", "metrics")

CONFIG = {
    "source": "a CPU-sized DiT for the benchmark's own tests",
    "family": "dit",
    "depth": 2, "hidden_size": 64, "num_heads": 4, "patch_size": 2,
    "input_size": 8, "in_channels": 4, "mlp_ratio": 4.0, "num_classes": 10,
    "learn_sigma": True, "model": "dit-xl2", "dtype": "bfloat16",
    "policy": "fastcache", "fastcache": {}, "slots": 2, "reduced": [],
    # bfloat16 against float32 at this size on the CPU, seeds 1-12: sound
    # runs read a first-step gap of at most 0.021 and a gated-step gap of
    # at most 0.0052, the float8 control at least 0.036 and 0.0119
    "check": {"sample": 3, "limits": {"first_step_gap": 0.03,
                                      "gated_step_gap": 0.009}},
}
MIX = {"arrival": "poisson", "rate": 12.0, "steps": {"8": 1},
       "guidance": {"1.0": 0.5, "4.0": 0.5}}
READER = '''"""Requests attempted in the window."""


def read(run):
    return float(len(run.window.requests))
'''

# the same network under a family of its own: its configuration names the
# sizes in words the harness has never seen, and its requests ask for even
# classes only
OWN_KEYS = {"layers": "depth", "width": "hidden_size", "heads": "num_heads",
            "patch": "patch_size", "side": "input_size",
            "channels": "in_channels", "mlp": "mlp_ratio",
            "classes": "num_classes", "sigma": "learn_sigma"}
OWN_CONFIG = dict({k: v for k, v in CONFIG.items()
                   if k not in OWN_KEYS.values()}, family="evendit",
                  **{k: CONFIG[v] for k, v in OWN_KEYS.items()})
OWN_FAMILY = f'''\
"""A class-conditional DiT whose configuration names its sizes in words of
its own and whose requests ask for even classes only: a model family that
only this copy of the benchmark has."""
import dataclasses
from pathlib import Path

from bench.spec import family

_dit = family({{"family": "dit"}}, Path(__file__).resolve().parents[2])
KEYS = {OWN_KEYS!r}


def _as_dit(cfg):
    return dict(cfg, **{{v: cfg[k] for k, v in KEYS.items()}})


def dims_of(cfg):
    return _dit.dims_of(_as_dit(cfg))


def algo_of(cfg):
    return _dit.algo_of(_as_dit(cfg))


def build(cfg, params, max_steps):
    return _dit.build(_as_dit(cfg), params, max_steps)


def conds(cfg, dims):
    return int(cfg["classes"]) // 2


def to_engine(r, clock):
    return _dit.to_engine(dataclasses.replace(r, cond=2 * r.cond), clock)


def reference_outputs(p32, dims, algo, sample, quant=False):
    return _dit.reference_outputs(
        p32, dims, algo, [dataclasses.replace(r, cond=2 * r.cond)
                          for r in sample], quant)


make_params = _dit.make_params
to_f32 = _dit.to_f32
gaps = _dit.gaps
rule_breaks = _dit.rule_breaks
shape_of = _dit.shape_of
request_flops = _dit.request_flops
kernel_costs = _dit.kernel_costs
'''


def make(tmp: Path) -> Path:
    """The copy, at ``tmp``; its new cells are ``tiny-short``, ``tiny-own``
    and ``tiny-r2``."""
    for sub in SUBDIRS:
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {"configs/tiny.json": json.dumps(CONFIG),
             "configs/tiny-own.json": json.dumps(OWN_CONFIG),
             "configs/tiny-r2.json": json.dumps(dict(CONFIG, replicas=2)),
             "families/evendit.py": OWN_FAMILY,
             "mixes/tiny-short.json": json.dumps(MIX),
             "metrics/attempted_n.py": READER}
    for name, text in files.items():
        (tmp / "bench" / name).write_text(text)
    for name, chips in (("tiny", 1), ("tiny-own", 1), ("tiny-r2", 2)):
        bench["configs"].append({"name": name, "source": CONFIG["source"],
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "tests"})
        cell = "tiny-short" if name == "tiny" else name
        bench["workloads"].append({"name": cell, "config": name,
                                   "traffic": "tiny-short", "chips": chips,
                                   "why": "tests"})
    bench["per_layer"].append({"name": "attempted_n", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine host loop",
                               "moves": "images_per_s",
                               "workloads": ["tiny-short"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
