"""A copy of the benchmark's files with one more cell, added only as new
files and new entries: a tiny configuration, a mix and a metric reader.
The tests drive it on the CPU."""
import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {
    "source": "a CPU-sized DiT for the benchmark's own tests",
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


def make(tmp: Path) -> Path:
    """The copy, at ``tmp``; its new cell is ``tiny-short``."""
    for sub in ("configs", "mixes", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    (tmp / "bench" / "mixes" / "tiny-short.json").write_text(json.dumps(MIX))
    (tmp / "bench" / "metrics" / "attempted_n.py").write_text(READER)
    bench["configs"].append({"name": "tiny", "source": CONFIG["source"],
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({"name": "tiny-short", "config": "tiny",
                               "traffic": "tiny-short", "chips": 1,
                               "why": "tests"})
    bench["per_layer"].append({"name": "attempted_n", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine host loop",
                               "moves": "images_per_s",
                               "workloads": ["tiny-short"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
