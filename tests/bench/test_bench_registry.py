"""BENCHMARK.json and the files it names: every cell, configuration, mix
and metric is found by name, and a cell added as new files is found with
no edit to a file that was there."""
import json
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tiny_cell  # noqa: E402
from bench import loadgen, weights  # noqa: E402
from bench.spec import (family, load_benchmark, load_cell,  # noqa: E402
                        metric_reader)

BENCH = load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads(name):
    cell = load_cell(name)
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(metric_reader(m["name"]))
    fam = family(cell.config)
    d = fam.dims_of(cell.config)
    fam.algo_of(cell.config)
    if cell.config["family"] == "dit":
        assert d == weights.dims_of(cell.config)
        assert d.head_dim * d.heads == d.hidden
        assert loadgen.max_steps(cell.mix) in (20, 50)
    assert int(cell.config.get("replicas", 1)) <= cell.chips
    assert cell.config["check"]["limits"]


def test_names_units_and_bounds():
    seen = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["name"] not in seen
        seen.add(m["name"])
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200


def test_configs_keep_published_widths():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["reduced"] == cfg["reduced"]
        assert len(c["source"]) <= 200
        family(cfg)
        if cfg["family"] != "dit":
            continue
        assert cfg["reduced"] == []
        assert (cfg["depth"], cfg["hidden_size"], cfg["num_heads"],
                cfg["mlp_ratio"], cfg["patch_size"]) == (28, 1152, 16, 4.0, 2)


def test_images_per_s_counts_the_steps_run_at_the_close():
    from bench.loadgen import Request
    from bench.results import RunData
    from bench.window import WindowResult
    reqs = [Request(rid=i, cond=0, steps=50, guidance=4.0, noise_seed=i)
            for i in range(4)]
    reqs[0].done_t = 9.0                  # finished inside the window
    reqs[1].done_t = 10.05                # finished in the close's turn
    # 2 and 3 were in their slots at the close, 25 and 10 steps in
    w = WindowResult(requests=reqs, seconds=10.0, close_s=10.1, busy_s=10.0,
                     model_steps=100, acc={}, in_flight={2: {}, 3: {}},
                     steps_done={2: 25, 3: 10}, compiles=0, drained_s=12.0)
    run = RunData(cell=None, dims=None, shape=None, algo=None, window=w,
                  setup_s=1.0)
    read = metric_reader("images_per_s")
    assert read(run) == pytest.approx((2 + 0.5 + 0.2) / 10.1)


def test_new_cell_is_found_from_new_files_alone(tmp_path):
    root = tiny_cell.make(tmp_path)
    cell = load_cell("tiny-short", root)
    assert cell.config["depth"] == 2 and cell.mix["rate"] == 12.0
    assert [m["name"] for m in cell.per_layer] == ["attempted_n"]
    assert metric_reader("attempted_n", root).__doc__ is None
    # the split names fall back to their base reader
    assert metric_reader("mfu.poisson", root) is not None
    for name in tiny_cell.SUBDIRS:
        before = sorted(p.name for p in (ROOT / "bench" / name).iterdir())
        after = sorted(p.name for p in (root / "bench" / name).iterdir())
        assert set(before) < set(after)
