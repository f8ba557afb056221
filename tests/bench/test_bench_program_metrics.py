"""The readers of the metrics that read what the program counts and names:
``block_waste_share`` from the engine's ``blocks_run`` counter, and
``attention_share`` from the device trace, its ops put down to the
program's named scopes through the compiled serve step
(``bench/scopes.py``)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

import tiny_cell  # noqa: E402
from bench import scopes  # noqa: E402
from bench.results import RunData  # noqa: E402
from bench.spec import load_cell, metric_reader  # noqa: E402
from bench.trace_reduce import Summary  # noqa: E402
from bench.window import WindowResult  # noqa: E402


def window(acc):
    return WindowResult(requests=[], seconds=1.0, close_s=1.0, busy_s=1.0,
                        model_steps=10, acc=acc, in_flight={},
                        steps_done={}, compiles=0, drained_s=1.0)


def run_data(acc=None, trace=None, cell=None):
    return RunData(cell=cell, dims=None, shape=None, algo=None,
                   window=window(acc or {}), setup_s=1.0, trace=trace)


@pytest.mark.parametrize("name", ["block_waste_share.poisson",
                                  "block_waste_share.backlog"])
def test_block_waste_share(name):
    read = metric_reader(name)
    acc = {"blocks_run": 280.0, "blocks_computed": 70.0,
           "blocks_skipped": 210.0}
    assert read(run_data(acc)) == pytest.approx(75.0)
    # a program that counts no blocks_run (the parent of this metric)
    assert read(run_data({"blocks_computed": 70.0})) is None


HLO = """\
HloModule jit_step

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.2 = f32[8]{0} multiply(%param_0, %param_0), \
metadata={op_name="jit(f)/model_eval/fastcache.block/attention/mul"}
}

%fused_computation.2 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %reshape.6 = f32[8]{0} reshape(%param_0), \
metadata={op_name="jit(f)/model_eval/fastcache.payload/select_n"}
  ROOT %scatter.7 = f32[8]{0} scatter(%param_0, %reshape.6), to_apply=%r
}

%fused_computation.3 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %fusion.8 = f32[8]{0} fusion(%param_0), kind=kCustom, \
calls=%fused_computation.2
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %fusion.9 = f32[8]{0} fusion(%p), kind=kCustom, calls=%fused_computation.3
  %fusion.4 = f32[8]{0} fusion(%fusion.3), kind=kOutput, \
calls=%fused_computation.1, metadata={op_name="jit(f)/model_eval/mlp/dot"}
  ROOT %copy.5 = f32[8]{0} copy(%fusion.4), \
metadata={op_name="jit(f)/ddim_update/copy"}
}
"""


def test_op_names_and_scopes():
    names = scopes.op_names(HLO)
    # a fusion with no op_name of its own takes its root's
    assert names["fusion.3"] == "jit(f)/model_eval/fastcache.block/attention/mul"
    assert names["fusion.4"] == "jit(f)/model_eval/mlp/dot"
    # ... through nested fusions to a root the compiler left unnamed, whose
    # computation's last named instruction it takes
    assert names["fusion.9"] == "jit(f)/model_eval/fastcache.payload/select_n"
    top = [("fusion.3", 0.2), ("fusion.4", 0.5), ("copy.5", 0.1),
           ("fused_gate", 0.05)]
    assert scopes.seconds_by_scope(top, names) == {
        "mlp": 0.5, "attention": 0.2, "ddim_update": 0.1, "other": 0.05}
    assert scopes.seconds_under(top, names, "attention") == 0.2
    assert scopes.seconds_under(top, names, "fastcache.block") == 0.2


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return load_cell("tiny-short", tiny_cell.make(tmp_path_factory.mktemp("b")))


def test_attention_share_reads_the_compiled_step(cell):
    """The tiny cell's serve step, compiled on the CPU: its attention ops
    are found by name, and the reader divides their traced time by the
    device's busy time."""
    names = scopes.op_names(scopes.serve_step_text(cell))
    att = sorted(n for n, o in names.items() if "attention" in o.split("/"))
    mlp = sorted(n for n, o in names.items() if scopes.scope_of(o) == "mlp")
    assert att and mlp
    top = [(att[0], 0.3), (att[-1], 0.1), (mlp[0], 1.2), ("unnamed.1", 0.4)]
    trace = Summary(window_s=4.0, busy_s=2.0, kernels={}, top_ops=top,
                    idle=[])
    read = metric_reader("attention_share.backlog")
    assert read(run_data(trace=trace, cell=cell)) == pytest.approx(20.0)
    assert read(run_data(cell=cell)) is None            # untraced
    none_under = Summary(window_s=4.0, busy_s=2.0, kernels={},
                         top_ops=[(mlp[0], 1.0)], idle=[])
    assert read(run_data(trace=none_under, cell=cell)) is None
