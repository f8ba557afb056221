"""Whole runs of a tiny cell on the CPU, the harness's look for a chip
skipped: a sound run is correct, and each fault that a served diffusion
cell can have, planted under the timed path, makes ``correct`` false; so
does the float8 control in the program's place, on a request's first step
and on its cached steps.  The cell itself is added to a copy of the
benchmark as new files only (``tiny_cell.py``)."""
import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE)]

import tiny_cell  # noqa: E402
from bench import check, reference, weights  # noqa: E402
from bench.calibrate import readings  # noqa: E402
from bench.families import dit  # noqa: E402
from bench.run import run_cell, serving, set_up  # noqa: E402
from bench.spec import load_cell  # noqa: E402
from repro.core import statcache  # noqa: E402

SECONDS = 1.5


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return load_cell("tiny-short", tiny_cell.make(tmp_path_factory.mktemp("b")))


def run(cell, hook=None, seed=5):
    return run_cell(cell, seed, SECONDS, False, t_start=time.perf_counter(),
                    require_chip=False, engine_hook=hook)


def frozen(eng, mp):
    """The step returns the latents and the cache state it was given."""
    impl = eng._serve_step_impl

    def step(params, state, x, *rest):
        _, _, acc, slot_acc, metrics = impl(params, state, x, *rest)
        return x, state, acc, slot_acc, metrics
    eng._step = jax.jit(step)


def half_batch(eng, mp):
    """The second half of the slots gets the first half's eps."""
    orig = eng.runner.step

    def step(params, state, latents, t, labels):
        eps, st = orig(params, state, latents, t, labels)
        s = eps.shape[0] // 2
        q = s // 2
        c, u = eps[:s], eps[s:]
        c = jnp.concatenate([c[:q], c[:s - q]])
        u = jnp.concatenate([u[:q], u[:s - q]])
        return jnp.concatenate([c, u]), st
    eng.runner.step = step


def altered(eng, mp):
    """One token of every slot's latents is altered where the step
    produces them."""
    impl = eng._serve_step_impl

    def step(*args):
        x, *rest = impl(*args)
        return (x.at[:, :2, :2, :].add(1.0), *rest)
    eng._step = jax.jit(step)


def gate_always_caches(eng, mp):
    """The chi-square gate caches every block whose tracker is warm."""
    mp.setattr(statcache, "make_threshold", lambda alpha, n: 1e30)


def wrong_blend(eng, mp):
    """The cached path blends with gamma 0.8 where the configuration
    states 0.5."""
    impl = eng.runner.impl
    impl.fc = dataclasses.replace(impl.fc, blend_gamma=0.8)


def stale_slot(eng, mp):
    """A slot handed to a new request keeps its variance trackers."""
    mp.setattr(statcache, "reset_gate_slot", lambda gate, slot: gate)


def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["check"]
    assert out["attempted"] == 18 and out["failed"] == 0
    assert list(out["check"]) == ["first_step_gap", "gated_step_gap",
                                  "cache_rule_breaks", "unfinished",
                                  "non_finite", "compiles_in_window"]
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"


def test_number_without_limit_is_shown_not_compared(cell, monkeypatch):
    """A limit stated as null leaves that number out of the verdict; the
    rest are still compared, so a fault that they read still fails."""
    check_cfg = dict(cell.config["check"],
                     limits=dict(cell.config["check"]["limits"],
                                 gated_step_gap=None))
    quiet = cell._replace(config=dict(cell.config, check=check_cfg))
    out = run(quiet)
    assert out["correct"], out["check"]
    assert "gated_step_gap" not in out["check"]
    assert "first_step_gap" in out["check"]
    out = run(quiet, lambda eng: gate_always_caches(eng, monkeypatch))
    assert not out["correct"], out["check"]


def test_new_metric_file_is_read(cell):
    out = run_cell(cell, 6, SECONDS, True, t_start=time.perf_counter(),
                   require_chip=False)
    assert out["metrics"]["attempted_n"]["value"] == 18.0
    assert out["correct"], out["check"]


@pytest.mark.parametrize("fault", [frozen, half_batch, altered,
                                   gate_always_caches, wrong_blend,
                                   stale_slot],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    out = run(cell, lambda eng: fault(eng, monkeypatch))
    assert not out["correct"], out["check"]


@pytest.mark.parametrize("fault,number", [
    (gate_always_caches, "cache_rule_breaks"),
    (wrong_blend, "gated_step_gap"),
    (stale_slot, "cache_rule_breaks")],
    ids=lambda v: getattr(v, "__name__", v))
def test_cached_path_fault_is_caught_there(cell, fault, number, monkeypatch):
    """The faults of the cached path pass the first step and fail the
    number that reads the cached steps."""
    out = run(cell, lambda eng: fault(eng, monkeypatch))
    shown = out["check"]
    assert shown["first_step_gap"]["value"] <= shown["first_step_gap"][
        "limit"], shown
    assert shown[number]["value"] > shown[number]["limit"], shown


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_the_cached_steps(cell, seed):
    """The float8 reference in the program's place, from the program's
    own inputs at the same cached steps, reads past the limit."""
    su = set_up(cell, require_chip=False)
    cfg = cell.config
    srv = serving(cfg, weights.make_params(su.dims, seed, cfg["dtype"]),
                  su.max_steps)
    row = readings(cell, su, srv, seed, SECONDS, control=True)
    assert row["gated_steps"] >= 3, row
    ok, _ = check.verdict(row["control"], cfg["check"]["limits"])
    assert not ok, row
    assert (row["control"]["gated_step_gap"]
            > cfg["check"]["limits"]["gated_step_gap"]), row


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_is_not_correct(cell, seed):
    cfg = cell.config
    d = weights.dims_of(cfg)
    algo = reference.algo_of(cfg)
    p32 = reference.to_f32(weights.make_params(d, seed, cfg["dtype"]))
    reqs = [r for r in tiny_requests(cell, seed)][:3]
    ref = dit.reference_outputs(p32, d, algo, reqs)
    ctl = dit.reference_outputs(p32, d, algo, reqs, quant=True)
    gap = dit.gaps(d, reqs, ctl, ref)["first_step_gap"]
    ok, _ = check.verdict({"first_step_gap": gap}, cfg["check"]["limits"])
    assert not ok, gap


def tiny_requests(cell, seed):
    from bench import loadgen
    return loadgen.poisson(cell.mix, seed, SECONDS, cell.config["num_classes"])


def test_reference_outputs_are_finite(cell):
    cfg = cell.config
    d = weights.dims_of(cfg)
    p32 = reference.to_f32(weights.make_params(d, 9, cfg["dtype"]))
    out = dit.reference_outputs(p32, d, reference.algo_of(cfg),
                                tiny_requests(cell, 9)[:2])
    assert all(np.isfinite(a).all() for steps in out.values()
               for a in steps.values())
