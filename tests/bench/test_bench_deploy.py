"""What a configuration deploys, found by file: a model family that exists
only as a new file in a copy of the benchmark runs end to end, and a
configuration of two replicas runs behind the program's router on two
(virtual) devices."""
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(HERE)]

import tiny_cell  # noqa: E402
from bench.run import run_cell  # noqa: E402
from bench.spec import load_cell  # noqa: E402

SECONDS = 1.5


def test_new_family_runs_from_new_files_alone(tmp_path):
    root = tiny_cell.make(tmp_path)
    for sub in tiny_cell.SUBDIRS:               # nothing that was there moved
        for path in (ROOT / "bench" / sub).glob("*"):
            if path.is_file():
                assert (root / "bench" / sub / path.name).read_bytes() == \
                    path.read_bytes()
    bench = json.loads((root / "BENCHMARK.json").read_text())
    before = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert bench[key][:len(before[key])] == before[key]
    cell = load_cell("tiny-own", root)
    assert "depth" not in cell.config and cell.config["layers"] == 2
    asked = []

    def record(eng):
        add = eng.add_request

        def add_request(req):
            asked.append(req)
            return add(req)
        eng.add_request = add_request

    out = run_cell(cell, 5, SECONDS, False, t_start=time.perf_counter(),
                   require_chip=False, engine_hook=record)
    assert out["correct"], out["check"]
    assert out["attempted"] == 18 and out["failed"] == 0
    served = [r.label for r in asked if r.rid >= 0]
    assert len(served) == 18 and all(c % 2 == 0 for c in served), served


RUN_R2 = """
import json, sys, time
from pathlib import Path
sys.path[:0] = sys.argv[1:4]
import jax
import tiny_cell
from bench.run import run_cell
from bench.spec import load_cell


def altered(eng):
    impl = eng._serve_step_impl

    def step(*args):
        x, *rest = impl(*args)
        return (x.at[:, :2, :2, :].add(1.0), *rest)
    eng._step = jax.jit(step)


cell = load_cell("tiny-r2", tiny_cell.make(Path(sys.argv[4])))
for hook in (None, altered):
    out = run_cell(cell, 7, 1.5, False, t_start=time.perf_counter(),
                   require_chip=False, engine_hook=hook)
    print(json.dumps(out), flush=True)
"""


def test_two_replicas_behind_the_router(tmp_path):
    """A sound run on both replicas is correct, and the same run with a
    token altered where every replica's step produces it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.run(
        [sys.executable, "-c", RUN_R2, str(ROOT), str(ROOT / "src"),
         str(HERE), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    sound, broken = [json.loads(line) for line in
                     proc.stdout.strip().splitlines()[-2:]]
    got = re.findall(r"requests admitted by replica: (\d+), (\d+);",
                     proc.stderr)
    assert len(got) == 2, proc.stderr[-4000:]
    admitted = [int(n) for n in got[0]]
    assert min(admitted) > 0 and sum(admitted) == sound["attempted"] == 18
    assert sound["correct"], sound["check"]
    assert sound["failed"] == 0 and sound["check"]["unfinished"]["value"] == 0
    assert sound["device"]["count"] == 2
    assert not broken["correct"], broken["check"]
