"""The serving engine's spans (``obs/tracing.py``): the span tree of a small
run, nothing recorded and nothing changed without a tracer, and the
in-memory spans on the clock of a ``jax.profiler`` trace of the same run."""
import collections

import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import FastCacheConfig
from repro.core import CachedDiT
from repro.models import build_model
from repro.models.dit import unzero_params
from repro.obs import TraceRecorder, tracing
from repro.serving import DiffusionRequest, DiffusionServingEngine
from tests.conftest import f32_cfg

pytestmark = pytest.mark.obs


@pytest.fixture(scope="module")
def dit():
    cfg = f32_cfg(get_reduced("dit-b2"))
    model = build_model(cfg)
    params = unzero_params(model.init(jax.random.PRNGKey(0)),
                           jax.random.PRNGKey(1))
    return model, params


def requests():
    # rid 1 is admitted mid-flight, rid 2 into rid 0's freed slot; they
    # end on engine steps 3, 4 and 7
    return [DiffusionRequest(rid=0, label=1, seed=10, arrival_step=0,
                             num_steps=3),
            DiffusionRequest(rid=1, label=2, seed=11, arrival_step=1,
                             num_steps=3),
            DiffusionRequest(rid=2, label=3, seed=12, arrival_step=2,
                             num_steps=4)]


def serve(dit, tracer):
    model, params = dit
    runner = CachedDiT(model, FastCacheConfig(), policy="fastcache")
    eng = DiffusionServingEngine(runner, params, max_slots=2, num_steps=4,
                                 guidance_scale=4.0, tracer=tracer)
    steps = []                          # (engine clock, rids finished)
    queue = requests()
    while queue or any(r is not None for r in eng.slots):
        while eng.free_slots() and queue and \
                queue[0].arrival_step <= eng.clock:
            eng.add_request(queue.pop(0))
        done = eng.step()
        steps.append((eng.clock, [r.rid for r in done]))
    return eng, steps


def test_span_tree(dit):
    tr = TraceRecorder()
    _, steps = serve(dit, tr)
    spans = tr.spans
    assert all(s is not None and s.t1 >= s.t0 for s in spans)
    top = [s for s in spans if s.parent is None]
    assert {s.name for s in top} == {"engine.admit", "engine.step"}
    # one engine.step per step() that ran the model, in order
    step_spans = [s for s in top if s.name == "engine.step"]
    assert [s.args["engine_step"] for s in step_spans] == [
        c for c, _ in steps]
    assert [s.args["active"] for s in step_spans] == [1, 2, 2, 2, 1, 1, 1]
    children = collections.defaultdict(list)
    for i, s in enumerate(spans):
        if s.parent is not None:
            assert spans[s.parent].t0 <= s.t0 <= s.t1 <= spans[s.parent].t1
            children[s.parent].append(s.name)
    admits = [i for i, s in enumerate(spans) if s.name == "engine.admit"]
    assert [spans[i].args for i in admits] == [
        {"rid": 0, "slot": 0}, {"rid": 1, "slot": 1}, {"rid": 2, "slot": 0}]
    for i in admits:
        assert children[i] == ["engine.admit.stage", "engine.admit.dispatch"]
    # engine.harvest only on the steps where a request ended
    for i, s in enumerate(spans):
        if s.name != "engine.step":
            continue
        ended = dict(steps)[s.args["engine_step"]]
        kids = ["engine.step.prepare", "engine.step.dispatch"]
        if ended:
            kids.append("engine.harvest")
            h = i + 3
            assert spans[h].name == "engine.harvest"
            assert spans[h].args == {"rids": ended}
            assert children[h] == ["engine.harvest.fetch",
                                   "engine.harvest.reset"]
        assert children[i] == kids
    assert sorted(r for _, rids in steps for r in rids) == [0, 1, 2]
    # the Chrome export names the step events by the span names
    names = [e["name"] for e in tr.to_json()["traceEvents"]]
    assert names.count("engine.step") == len(step_spans)
    assert names.count("engine.harvest.fetch") == 3


def test_no_tracer_records_nothing_and_changes_nothing(dit, monkeypatch):
    built = []
    orig = tracing._SpanCtx.__init__

    def count(self, *a, **k):
        built.append(a[1])
        orig(self, *a, **k)

    monkeypatch.setattr(tracing._SpanCtx, "__init__", count)
    plain, _ = serve(dit, None)
    assert built == []
    traced, _ = serve(dit, TraceRecorder(capture_slots=True))
    assert built
    np.testing.assert_array_equal(np.asarray(plain.x),
                                  np.asarray(traced.x))
    for a, b in zip(jax.tree.leaves((plain.state, plain.acc,
                                     plain.slot_acc, plain.metrics)),
                    jax.tree.leaves((traced.state, traced.acc,
                                     traced.slot_acc, traced.metrics))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_spans_on_the_profiler_clock(dit, tmp_path):
    from jax.profiler import ProfileData
    serve(dit, None)                     # compile outside the trace
    tr = TraceRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve(dit, tr)
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                events += [ev for ev in line.events
                           if ev.name.startswith("engine.")]
    events.sort(key=lambda ev: ev.start_ns)
    mine = sorted(tr.spans, key=lambda s: s.t0)
    assert [ev.name for ev in events] == [s.name for s in mine]
    t0, p0 = mine[0].t0, events[0].start_ns
    for s, ev in zip(mine, events):
        assert abs((s.t1 - s.t0) - ev.duration_ns) < 200_000, s.name
        assert abs((s.t0 - t0) - (ev.start_ns - p0)) < 200_000, s.name
    rids = [dict(ev.stats).get("rid") for ev in events
            if ev.name == "engine.admit"]
    assert [int(r) for r in rids] == [0, 1, 2]
