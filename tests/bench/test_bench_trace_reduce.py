"""bench/trace_reduce.py: the busy union, the idle share and its
attribution to host spans on made-up events, and the kernels, busy time
and idle share of a small trace recorded on a TPU v5e (4 admissions and
3 serve steps of dit-xl2-512 with token merging)."""
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import trace_reduce as tr  # noqa: E402

MS = 1_000_000


def test_union_idle_and_attribution():
    spans = [(0, 10 * MS, "admit"), (10 * MS, 40 * MS, "step"),
             (40 * MS, 100 * MS, "sleep")]
    ops = [(5 * MS, 20 * MS, "%fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop"),
           (15 * MS, 30 * MS, "%fused_gate = (f32[8,1,1]{2,1,0}) "
                              "custom-call(bf16[8,128,1152] %b)"),
           (50 * MS, 60 * MS, "%fusion.7 = bf16[8]{0} fusion(%c), kind=kLoop"),
           (0, 100 * MS, "%while.3 = (s32[]) while(%t), body=%body"),
           (120 * MS, 130 * MS, "%late = f32[] copy(%d)")]
    s = tr.summarize(spans, [ops])
    assert s.window_s == pytest.approx(0.1)
    # the while loop spans everything: busy, but not a leaf op
    assert s.busy_s == pytest.approx(0.1)
    assert s.kernels == {"fused_gate": (1, pytest.approx(0.015))}
    assert dict(s.top_ops) == {"fusion.1": pytest.approx(0.015),
                               "fused_gate": pytest.approx(0.015),
                               "fusion.7": pytest.approx(0.01)}
    s = tr.summarize(spans, [[o for o in ops if "while" not in o[2]]])
    assert s.busy_s == pytest.approx(0.035)           # 5-30 and 50-60 ms
    idle = dict(s.idle)
    # 0-5 admit, 30-40 step, 40-50 and 60-100 sleep
    assert idle == {"admit": pytest.approx(0.005), "step": pytest.approx(0.01),
                    "sleep": pytest.approx(0.05)}
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    b = s.breakdown()
    assert b["idle_gaps"][0] == ["sleep", pytest.approx(0.05)]


def test_window_ends_at_the_close():
    spans = [(0, 10 * MS, "step"), (10 * MS, 20 * MS, "close"),
             (20 * MS, 90 * MS, "harvest")]
    s = tr.summarize(spans, [[(0, 5 * MS, "%f = f32[] copy(%b)")]])
    assert s.window_s == pytest.approx(0.02)
    assert dict(s.idle) == {"step": pytest.approx(0.005),
                            "close": pytest.approx(0.01)}


def test_nothing_to_read_is_none():
    assert tr.summarize([], [[(0, 1, "%a = f32[] copy(%b)")]]) is None
    assert tr.summarize([(0, 1, "step")], []) is None


def test_op_names():
    assert tr.op_name("%cond.3.clone.6 = (bf16[8]{0:T(8,128)}) "
                      "conditional(s32[] %c)") == ("cond.3.clone.6", "cond",
                                                   "conditional")
    assert tr.op_name("%merge_assign.1 = (bf16[512,8,1152]{2,1,0:T(8,128)"
                      "(2,1)}, s32[512]) custom-call(bf16[512,16,1152] %h)"
                      ) == ("merge_assign.1", "merge_assign", "custom-call")


def test_recorded_v5e_trace():
    """4 admissions and 3 serve steps of dit-xl2-512 with merging, traced on
    a TPU v5e (pruned to the device's XLA ops and the benchmark's spans):
    one cold step, then two gated steps of 28 fused-gate calls each, and
    the three merge kernels once a step."""
    s = tr.reduce(HERE / "data" / "v5e_xl512_3steps.xplane.pb")
    assert s.window_s == pytest.approx(0.1031, abs=1e-4)
    assert 0 < s.busy_s < s.window_s
    assert 1 - s.busy_s / s.window_s == pytest.approx(0.168, abs=0.005)
    assert {k: c for k, (c, _) in s.kernels.items()} == {
        "fused_gate": 56, "knn_density": 3, "merge_assign": 3,
        "unmerge_scatter": 3}
    assert s.kernels["merge_assign"][1] == pytest.approx(0.0036, abs=1e-4)
    assert not any(n.startswith(("while", "cond")) for n, _ in s.top_ops)
    assert sum(v for _, v in s.idle) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert [n for n, _ in s.idle][0] == "admit"
