"""The observability subsystem: metric registry discipline, pure-jnp
device-plane updates, MetricsCollector harvest/export round-trips
(Prometheus text + JSONL windows), Chrome/Perfetto trace recording, the
calibration recorder's .npz contract, and the end-to-end engine wiring.

Run via ``make test-obs`` (CI job of the same name)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import FastCacheConfig
from repro.core import CachedDiT
from repro.core.policies.smoothcache import smooth_schedule_from_errors
from repro.models import build_model
from repro.obs import (METRICS, MetricsCollector, TraceRecorder, counter,
                       histogram, init_device_metrics, load_calibration,
                       parse_prometheus, record_calibration,
                       save_calibration, validate_trace)
from repro.obs import metrics as obs_metrics
from repro.serving import DiffusionRequest, DiffusionServingEngine
from tests.conftest import f32_cfg

pytestmark = pytest.mark.obs


@pytest.fixture(scope="module")
def dit():
    cfg = f32_cfg(get_reduced("dit-b2"))
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_duplicate_registration_with_different_spec_raises():
    name = counter("_obs_test_probe_total", "probe")
    try:
        # identical re-registration is idempotent (module reloads)
        assert counter("_obs_test_probe_total", "probe") == name
        with pytest.raises(ValueError, match="already registered"):
            counter("_obs_test_probe_total", "different help")
        with pytest.raises(ValueError, match="already registered"):
            histogram("_obs_test_probe_total", "now a histogram")
    finally:
        del METRICS[name]


def test_invalid_metric_names_and_buckets_raise():
    with pytest.raises(ValueError, match="not a valid"):
        counter("bad-name")
    with pytest.raises(ValueError, match="ascending"):
        histogram("_obs_test_bad_buckets", buckets=(2, 1))
    with pytest.raises(ValueError, match="ascending"):
        histogram("_obs_test_dup_buckets", buckets=(1, 1, 2))
    assert "_obs_test_bad_buckets" not in METRICS


def test_serving_metric_set_is_registered():
    for n in (obs_metrics.DEVICE_COUNTERS + obs_metrics.DEVICE_HISTOGRAMS
              + obs_metrics.DEVICE_PER_SLOT):
        assert n in METRICS


# ---------------------------------------------------------------------------
# Device plane
# ---------------------------------------------------------------------------

def test_device_updates_are_pure_and_jit_consistent():
    m = init_device_metrics(4)
    m2 = obs_metrics.inc(m, obs_metrics.SERVE_STEPS, 2.0)
    assert float(m["counters"][obs_metrics.SERVE_STEPS]) == 0.0
    assert float(m2["counters"][obs_metrics.SERVE_STEPS]) == 2.0

    def update(mm):
        mm = obs_metrics.inc(mm, obs_metrics.SERVE_STEPS, 1.0)
        mm = obs_metrics.observe(mm, obs_metrics.ACTIVE_SLOTS, 3.0)
        return obs_metrics.slot_add(mm, obs_metrics.SLOT_ACTIVE_STEPS,
                                    jnp.ones((4,), jnp.float32))

    eager, jitted = update(m), jax.jit(update)(m)
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    h = eager["hist"][obs_metrics.ACTIVE_SLOTS]
    # active_slots buckets (0, 1, 2, 4, ...): 3.0 lands in the le=4 bin
    assert float(h["bucket"][3]) == 1.0 and float(h["count"]) == 1.0
    assert float(h["sum"]) == 3.0


def test_histogram_overflow_bin():
    m = init_device_metrics(1)
    m = obs_metrics.observe(m, obs_metrics.ACTIVE_SLOTS, 1e9)
    h = m["hist"][obs_metrics.ACTIVE_SLOTS]
    assert float(h["bucket"][-1]) == 1.0  # +Inf overflow bin


# ---------------------------------------------------------------------------
# Host plane: collector, harvest, exports
# ---------------------------------------------------------------------------

def test_collector_kind_mismatch_and_window_validation():
    c = MetricsCollector()
    with pytest.raises(ValueError, match="not a counter"):
        c.inc(obs_metrics.REQUEST_LATENCY)
    with pytest.raises(ValueError, match="not a histogram"):
        c.observe(obs_metrics.ADMISSIONS, 1.0)
    with pytest.raises(ValueError, match="unknown metric"):
        c.inc("never_registered_total")
    with pytest.raises(ValueError, match="window_steps"):
        MetricsCollector(window_steps=0)


def test_harvest_merges_host_and_device_planes():
    c = MetricsCollector(labels={"policy": "fastcache"})
    c.inc(obs_metrics.ADMISSIONS, 3)
    c.observe(obs_metrics.REQUEST_LATENCY, 10.0)
    m = init_device_metrics(2)
    m = obs_metrics.inc(m, obs_metrics.SERVE_STEPS, 5.0)
    w = c.harvest(m, at_step=7)
    assert w["at_step"] == 7 and w["labels"] == {"policy": "fastcache"}
    totals = c.totals()
    assert totals[obs_metrics.ADMISSIONS] == 3.0
    assert totals[obs_metrics.SERVE_STEPS] == 5.0
    # harvest is cumulative, not a delta: a second harvest of the same
    # device tree reports the same totals
    c.harvest(m, at_step=8)
    assert c.totals()[obs_metrics.SERVE_STEPS] == 5.0
    assert len(c.windows) == 2


def test_prometheus_round_trip():
    c = MetricsCollector(labels={"policy": "fora", "dit": "dit-b2"})
    c.inc(obs_metrics.ADMISSIONS, 2)
    for v in (3.0, 9.0, 1000.0):
        c.observe(obs_metrics.REQUEST_LATENCY, v)
    c.set_gauge("run_wall_seconds", 1.25)
    text = c.to_prometheus()
    parsed = parse_prometheus(text)
    adm = parsed["repro_" + obs_metrics.ADMISSIONS]
    assert adm["type"] == "counter"
    assert adm["samples"][0] == ({"dit": "dit-b2", "policy": "fora"}, 2.0)
    lat = parsed["repro_" + obs_metrics.REQUEST_LATENCY]
    assert lat["type"] == "histogram"
    by_le = {s[0]["le"]: s[1] for s in lat["samples"] if "le" in s[0]}
    # cumulative le-buckets must be monotone and end at count == 3
    cum = [by_le[k] for k in sorted(by_le, key=float)]
    assert cum == sorted(cum) and by_le["+Inf"] == 3.0
    assert parsed["repro_run_wall_seconds"]["type"] == "gauge"


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError, match="malformed"):
        parse_prometheus("this is { not exposition\n")


def test_prometheus_round_trip_escaped_label_values():
    """Label values carrying the three characters the text format escapes
    (backslash, double quote, newline) must survive export -> parse."""
    nasty = 'a\\b"c\nd'
    c = MetricsCollector(labels={"policy": nasty, "plain": "ok"})
    c.inc(obs_metrics.ADMISSIONS, 1)
    text = c.to_prometheus()
    # the raw exposition must stay line-oriented: no literal newline may
    # leak out of the quoted label value
    sample_lines = [ln for ln in text.splitlines()
                    if ln and not ln.startswith("#")]
    assert all("admissions" in ln or "policy" not in ln
               for ln in sample_lines)
    parsed = parse_prometheus(text)
    labels, value = parsed["repro_" + obs_metrics.ADMISSIONS]["samples"][0]
    assert labels == {"policy": nasty, "plain": "ok"}
    assert value == 1.0
    # a quote inside a label value must not terminate label scanning early
    assert parse_prometheus(
        'm{a="x\\"y",b="z"} 2\n')["m"]["samples"][0] \
        == ({"a": 'x"y', "b": "z"}, 2.0)
    with pytest.raises(ValueError, match="unterminated|malformed"):
        parse_prometheus('m{a="never closed\n')


def test_prometheus_round_trip_inf_buckets():
    """The implicit +Inf overflow bucket and observations beyond the last
    finite bound round-trip as +Inf, not a float-repr like 'inf'."""
    c = MetricsCollector()
    c.observe(obs_metrics.REQUEST_LATENCY, 1e12)  # overflow bin
    text = c.to_prometheus()
    assert 'le="+Inf"' in text
    parsed = parse_prometheus(text)
    lat = parsed["repro_" + obs_metrics.REQUEST_LATENCY]
    by_le = {s[0]["le"]: s[1] for s in lat["samples"] if "le" in s[0]}
    assert by_le["+Inf"] == 1.0
    assert all(v == 0.0 for le, v in by_le.items() if le != "+Inf")
    # explicit ±Inf sample VALUES parse too (gauges may legitimately hit)
    parsed = parse_prometheus("g 1\nh +Inf\ni -Inf\n")
    assert parsed["h"]["samples"][0][1] == float("inf")
    assert parsed["i"]["samples"][0][1] == float("-inf")


def test_prometheus_round_trip_nan_gauge():
    """A NaN gauge (e.g. a 0/0 ratio window) must export as the canonical
    'NaN' token and parse back to a float NaN rather than erroring."""
    c = MetricsCollector()
    c.set_gauge("empty_window_ratio", float("nan"))
    text = c.to_prometheus()
    assert "NaN" in text
    parsed = parse_prometheus(text)
    val = parsed["repro_empty_window_ratio"]["samples"][0][1]
    assert val != val  # NaN is the only float unequal to itself
    # arbitrary-case NaN tokens are rejected — only canonical spellings
    with pytest.raises(ValueError, match="malformed"):
        parse_prometheus("g not_a_number\n")


def test_jsonl_windows():
    c = MetricsCollector()
    c.inc(obs_metrics.ADMISSIONS)
    c.harvest(at_step=4)
    c.inc(obs_metrics.ADMISSIONS)
    c.harvest(at_step=8)
    lines = c.to_jsonl().strip().splitlines()
    assert len(lines) == 2
    w0, w1 = (json.loads(ln) for ln in lines)
    assert w0["at_step"] == 4 and w1["at_step"] == 8
    assert w1["counters"][obs_metrics.ADMISSIONS] == 2.0


# ---------------------------------------------------------------------------
# Trace recorder
# ---------------------------------------------------------------------------

def test_trace_recorder_round_trip(tmp_path):
    rec = TraceRecorder(capture_slots=True)
    rec.admit(0, 0, label=3, num_steps=4, engine_step=0)
    acc0 = {"steps_reused": jnp.zeros((2,), jnp.float32)}
    acc1 = {"steps_reused": jnp.array([1.0, 0.0], jnp.float32)}
    active = np.array([True, False])
    with rec.span("engine.step", engine_step=1, active=1):
        pass
    rec.snapshot_slots(1, active, acc0)
    with rec.span("engine.step", engine_step=2, active=1):
        pass
    rec.snapshot_slots(2, active, acc1)
    rec.finish(0, engine_step=2, stats={"steps_reused": 1.0})
    doc = rec.to_json()
    validate_trace(doc)
    names = [e["name"] for e in doc["traceEvents"]]
    assert "admit" in names and "finish" in names
    assert "request rid=0" in names and "engine.step" in names
    # slot 0's accumulator moved between snapshots -> a cache-reuse slice
    assert "denoise (cache reuse)" in names
    assert doc["displayTimeUnit"] == "ms"
    p = tmp_path / "trace.json"
    rec.write(str(p))
    validate_trace(json.loads(p.read_text()))


def test_validate_trace_rejects_bad_docs():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({"events": []})
    with pytest.raises(ValueError, match="missing ts/dur"):
        validate_trace({"traceEvents": [
            {"name": "x", "ph": "X", "pid": 0}]})
    with pytest.raises(ValueError, match="unknown phase"):
        validate_trace({"traceEvents": [
            {"name": "x", "ph": "Z", "pid": 0}]})
    with pytest.raises(ValueError, match="missing ts"):
        validate_trace({"traceEvents": [
            {"name": "x", "ph": "C", "pid": 0, "args": {"v": 1.0}}]})
    with pytest.raises(ValueError, match="no series args"):
        validate_trace({"traceEvents": [
            {"name": "x", "ph": "C", "pid": 0, "ts": 1.0}]})


def test_trace_counter_tracks():
    """Perfetto counter tracks (ph="C") from the cumulative snapshots:
    the running cache ratio always, the running mean audit error when the
    audit plane's accumulators ride the slot stats."""
    rec = TraceRecorder(capture_slots=True)
    active = np.array([True, True])
    snaps = [
        {"blocks_computed": jnp.array([4.0, 4.0]),
         "blocks_skipped": jnp.array([0.0, 0.0]),
         "audit_err_sum": jnp.array([0.0, 0.0]),
         "audit_steps": jnp.array([0.0, 0.0])},
        {"blocks_computed": jnp.array([6.0, 6.0]),
         "blocks_skipped": jnp.array([2.0, 2.0]),
         "audit_err_sum": jnp.array([0.3, 0.1]),
         "audit_steps": jnp.array([2.0, 2.0])},
    ]
    for step, st in enumerate(snaps):
        rec.snapshot_slots(step, active, st)
    doc = rec.to_json()
    validate_trace(doc)
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    ratios = [e["args"]["cache_ratio"] for e in counters
              if e["name"] == "cache ratio (running)"]
    errs = [e["args"]["audit_err_mean"] for e in counters
            if e["name"] == "audit error (running mean)"]
    assert ratios == [0.0, 4.0 / 16.0]
    assert errs[0] == 0.0 and np.isclose(errs[1], 0.4 / 4.0)
    # without audit accumulators only the cache-ratio track is emitted
    rec2 = TraceRecorder(capture_slots=True)
    rec2.snapshot_slots(0, active,
                       {"blocks_computed": jnp.array([4.0, 4.0])})
    names = [e["name"] for e in rec2.to_json()["traceEvents"]
             if e["ph"] == "C"]
    assert names == ["cache ratio (running)"]


# ---------------------------------------------------------------------------
# Calibration recorder
# ---------------------------------------------------------------------------

def test_calibration_round_trip_feeds_smoothcache(dit, tmp_path):
    cfg, model, params = dit
    runner = CachedDiT(model, FastCacheConfig(), policy="nocache")
    res = record_calibration(runner, params, batch=2, num_steps=4,
                             guidance_scale=4.0, seed=0)
    L = runner.L
    assert res["rel_delta"].shape == (4, L, 4)   # CFG doubles the batch
    assert res["errors_mean"].shape == (L, 4)
    np.testing.assert_array_equal(res["rel_delta"][0], 1.0)
    assert np.all(res["rel_delta"][1:] > 0.0)
    path = str(tmp_path / "calib.npz")
    save_calibration(path, res)
    loaded = load_calibration(path)
    np.testing.assert_array_equal(loaded["errors_mean"],
                                  res["errors_mean"])
    sched = smooth_schedule_from_errors(loaded["errors_mean"],
                                        threshold=0.5)
    assert sched.shape == (L, 4)
    assert not bool(sched[:, 0].any())  # column 0 always computes


def test_calibration_refuses_caching_policy(dit):
    cfg, model, params = dit
    runner = CachedDiT(model, FastCacheConfig(), policy="fastcache")
    with pytest.raises(ValueError, match="uncached"):
        record_calibration(runner, params, batch=1, num_steps=2)


def test_load_calibration_rejects_foreign_npz(tmp_path):
    p = str(tmp_path / "other.npz")
    np.savez(p, foo=np.zeros(3))
    with pytest.raises(ValueError, match="calibration artifact"):
        load_calibration(p)


# ---------------------------------------------------------------------------
# End-to-end engine wiring
# ---------------------------------------------------------------------------

def test_engine_metrics_and_trace_end_to_end(dit):
    cfg, model, params = dit
    runner = CachedDiT(model, FastCacheConfig(), policy="fastcache")
    collector = MetricsCollector(labels={"policy": "fastcache"},
                                 window_steps=4)
    tracer = TraceRecorder()
    eng = DiffusionServingEngine(runner, params, max_slots=2, num_steps=8,
                                 guidance_scale=4.0, collector=collector,
                                 tracer=tracer)
    reqs = [DiffusionRequest(rid=i, label=i + 1, seed=10 + i,
                             arrival_step=i) for i in range(3)]
    done = eng.run(reqs)
    assert sorted(r.rid for r in done) == [0, 1, 2]

    totals = collector.totals()
    assert totals[obs_metrics.ADMISSIONS] == 3.0
    assert totals[obs_metrics.REQUESTS_FINISHED] == 3.0
    assert totals[obs_metrics.SERVE_STEPS] == eng.model_steps
    # every request holds a slot for exactly its 8-step plan
    assert totals[obs_metrics.ACTIVE_SLOT_STEPS] == 24.0
    per_slot = collector.windows[-1]["per_slot"]
    assert sum(per_slot[obs_metrics.SLOT_ACTIVE_STEPS]) == 24.0
    # periodic windows (every 4 steps) plus the run-end harvest
    assert len(collector.windows) >= 2
    parse_prometheus(collector.to_prometheus())

    doc = tracer.to_json()
    validate_trace(doc)
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("admit") == 3 and names.count("finish") == 3
    assert any(n.startswith("request rid=") for n in names)


def test_engine_metrics_disabled_is_supported(dit):
    """enable_metrics=False traces a metrics-free step (the A/B baseline
    for the telemetry-overhead row in BENCH_serving.json)."""
    cfg, model, params = dit
    runner = CachedDiT(model, FastCacheConfig(), policy="fastcache")
    eng = DiffusionServingEngine(runner, params, max_slots=2, num_steps=4,
                                 guidance_scale=4.0, enable_metrics=False)
    assert eng.metrics == {}
    done = eng.run([DiffusionRequest(rid=0, label=1, seed=3,
                                     arrival_step=0)])
    assert len(done) == 1 and eng.harvest_metrics() is None
