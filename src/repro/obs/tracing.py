"""The serving engine's spans and per-request trace events, exported as
Chrome/Perfetto trace JSON.

Tracing is a **diagnostic mode**, on only where an engine is given a
``TraceRecorder``; with none attached the engine builds no span and
records nothing.  It is allowed to keep host-side state per engine step.

**Spans** (``TraceRecorder.span``): a span is a fixed name (step numbers
and request ids ride its args, never its name) timed on the host with
``time.perf_counter_ns`` into the in-memory record ``spans``, and written
at the same time as a ``jax.profiler.TraceAnnotation`` with the same name
and args, so a profiler trace holds it on the clock of the device's ops.
The engines open:

  engine.admit (rid, slot)           one admission
    engine.admit.stage               noise, plan rows and their staging
    engine.admit.dispatch            the fused admission program
  engine.resume (rid)                re-admission from a snapshot
  engine.preempt (rid)               a slot checkpointed out
  engine.step (engine_step, active)  one ``step()`` that runs the model
    engine.step.prepare              audit flag and the step's host arrays
    engine.step.dispatch             the serve step's enqueue (JAX
                                     dispatch is asynchronous: this is
                                     not the step's device time)
    engine.harvest (rids)            only on steps where requests finish
      engine.harvest.fetch           finished latents and counters to the
                                     host, waiting for the step itself
      engine.harvest.reset           the freed slots' reset dispatches

**Per-slot snapshots** (``capture_slots=True``, opt-in): after each step
the slots' accumulators are copied on the device (``jnp.add(v, 0)`` of
the donated buffers) and fetched only at :meth:`TraceRecorder.finalize`,
where consecutive diffs become per-slot "denoise" slices annotated with
the policy's skip/compute decision and counter tracks (running cache
ratio, running mean audited error).  They cost a device copy a step.

Chrome export (``displayTimeUnit: ms``): each span as a ``ph="X"`` event
on the engine-loop track; per-request "request" spans (admit -> finish)
and ``ph="i"`` "admit" / "finish" markers on a per-slot track; the
denoise slices and ``ph="C"`` counter tracks when snapshots were taken.

Inside the jitted step, ``jax.named_scope``s name the sampler's phases
(``diffusion/sampler.py``), the DiT block's parts (``adaln``,
``attention``, ``mlp``), FastCache's stages (``fastcache.*``) and token
merging (``merge``, ``unmerge``): they are op metadata only.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

_US = 1e3  # trace timestamps are microseconds; the clock counts ns

# what an engine opens where no tracer is attached: one shared no-op
NO_SPAN = contextlib.nullcontext()


class Span(NamedTuple):
    name: str
    t0: int                 # time.perf_counter_ns at open
    t1: int                 # ... and at close
    parent: Optional[int]   # index in TraceRecorder.spans, None at top
    args: Dict[str, Any]


def span(tracer: Optional["TraceRecorder"], name: str, **args):
    """``tracer.span(name, **args)``, or ``NO_SPAN`` without a tracer."""
    return NO_SPAN if tracer is None else tracer.span(name, **args)


class TraceRecorder:
    """Collects spans and trace events on the host; ``finalize()`` resolves
    deferred slot snapshots and ``write()`` emits Chrome/Perfetto JSON."""

    def __init__(self, *, pid: int = 0, capture_slots: bool = False):
        self.pid = pid
        self.capture_slots = capture_slots
        # in opening order; a span still open is None
        self.spans: List[Optional[Span]] = []
        self.events: List[Dict[str, Any]] = []
        self._t0 = time.perf_counter_ns()
        self._open: List[int] = []             # indices of open spans
        self._snapshots: List[Dict[str, Any]] = []  # deferred device copies
        self._requests: Dict[int, Dict[str, Any]] = {}
        self._finalized = False

    # -- clocks ---------------------------------------------------------

    def _now(self) -> float:
        return (time.perf_counter_ns() - self._t0) / _US

    # -- spans ----------------------------------------------------------

    def span(self, name: str, **args) -> "_SpanCtx":
        """A context manager timing ``name`` into ``spans`` and, as a
        ``jax.profiler.TraceAnnotation`` with ``args`` as its stats, into
        any profiler trace that is running."""
        return _SpanCtx(self, name, args)

    # -- request lifecycle ---------------------------------------------

    def admit(self, rid: int, slot: int, *, label: int = -1,
              num_steps: int = -1, engine_step: int = -1) -> None:
        ts = self._now()
        self._requests[rid] = {"slot": slot, "t_admit": ts,
                               "admit_step": engine_step}
        self.events.append({
            "name": "admit", "ph": "i", "ts": ts, "pid": self.pid,
            "tid": slot + 1, "cat": "request", "s": "t",
            "args": {"rid": rid, "label": label, "num_steps": num_steps,
                     "engine_step": engine_step}})

    def finish(self, rid: int, *, engine_step: int = -1,
               stats: Optional[Dict[str, float]] = None) -> None:
        ts = self._now()
        info = self._requests.pop(rid, None)
        slot = info["slot"] if info else 0
        self.events.append({
            "name": "finish", "ph": "i", "ts": ts, "pid": self.pid,
            "tid": slot + 1, "cat": "request", "s": "t",
            "args": {"rid": rid, "engine_step": engine_step,
                     **(stats or {})}})
        if info is not None:
            self.events.append({
                "name": f"request rid={rid}", "ph": "X",
                "ts": info["t_admit"], "dur": ts - info["t_admit"],
                "pid": self.pid, "tid": slot + 1, "cat": "request",
                "args": {"rid": rid, "admit_step": info["admit_step"],
                         "finish_step": engine_step, **(stats or {})}})

    # -- per-slot snapshots (opt-in) ------------------------------------

    def snapshot_slots(self, engine_step: int, active_rows,
                       slot_stats: Dict[str, Any]) -> None:
        """Defer a per-slot accumulator snapshot.  ``slot_stats`` holds
        *donated* device buffers — we enqueue dispatched copies (cheap
        async device work, no sync) and fetch them all in finalize()."""
        if not self.capture_slots or self._finalized:
            return
        self._snapshots.append({
            "engine_step": engine_step,
            "ts": self._now(),
            "active": jnp.add(jnp.asarray(active_rows, jnp.float32), 0.0),
            "stats": {k: jnp.add(v, 0.0) for k, v in slot_stats.items()},
        })

    # -- finalize / export ---------------------------------------------

    def finalize(self) -> None:
        """Fetch deferred snapshots (the single sync) and turn consecutive
        diffs into per-slot per-step "denoise" slices annotated with the
        policy's skip/compute decision, plus Perfetto counter tracks
        (``ph="C"``) for the running cache ratio and — when the audit
        plane's accumulators ride the snapshots — the running mean
        audited error."""
        if self._finalized:
            return
        self._finalized = True
        snaps = [{"engine_step": s["engine_step"], "ts": s["ts"],
                  "active": np.asarray(s["active"]),
                  "stats": {k: np.asarray(v)
                            for k, v in s["stats"].items()}}
                 for s in self._snapshots]
        self._snapshots = []
        self._emit_counter_tracks(snaps)
        for prev, cur in zip(snaps, snaps[1:]):
            dur = max(cur["ts"] - prev["ts"], 1.0)
            d = {k: cur["stats"][k] - prev["stats"][k]
                 for k in cur["stats"]}
            active = prev["active"]
            n_slots = active.shape[0]
            for s in range(n_slots):
                if active[s] <= 0.0:
                    continue
                args = {"engine_step": prev["engine_step"]}
                for k, v in d.items():
                    args[k] = float(v[s])
                skipped = args.get("steps_reused", 0.0) > 0.0
                self.events.append({
                    "name": "denoise (cache reuse)" if skipped
                    else "denoise (compute)",
                    "ph": "X", "ts": prev["ts"], "dur": dur,
                    "pid": self.pid, "tid": s + 1, "cat": "denoise",
                    "args": args})

    def _emit_counter_tracks(self, snaps: List[Dict[str, Any]]) -> None:
        """Counter-track events from the cumulative per-slot snapshots:
        Perfetto renders each ``args`` key of a same-named ``ph="C"``
        event series as a stacked counter plot.  The snapshots are
        running totals, so each point is a cumulative ratio — the curves
        converge to the run's headline numbers."""
        for s in snaps:
            st = s["stats"]
            if "blocks_computed" in st:
                skipped = float(np.sum(st.get("blocks_skipped", 0.0)))
                computed = float(np.sum(st["blocks_computed"]))
                total = skipped + computed
                self.events.append({
                    "name": "cache ratio (running)", "ph": "C",
                    "ts": s["ts"], "pid": self.pid, "cat": "counter",
                    "args": {"cache_ratio":
                             skipped / total if total else 0.0}})
            if "audit_err_sum" in st and "audit_steps" in st:
                err = float(np.sum(st["audit_err_sum"]))
                steps = float(np.sum(st["audit_steps"]))
                self.events.append({
                    "name": "audit error (running mean)", "ph": "C",
                    "ts": s["ts"], "pid": self.pid, "cat": "counter",
                    "args": {"audit_err_mean":
                             err / steps if steps else 0.0}})

    def to_json(self) -> Dict[str, Any]:
        self.finalize()
        meta = [{"name": "process_name", "ph": "M", "pid": self.pid,
                 "args": {"name": "repro serving engine"}},
                {"name": "thread_name", "ph": "M", "pid": self.pid,
                 "tid": 0, "args": {"name": "engine loop"}}]
        tids = sorted({e.get("tid", 0) for e in self.events} - {0})
        for tid in tids:
            meta.append({"name": "thread_name", "ph": "M", "pid": self.pid,
                         "tid": tid, "args": {"name": f"slot {tid - 1}"}})
        spans = [{"name": s.name, "ph": "X", "ts": (s.t0 - self._t0) / _US,
                  "dur": max((s.t1 - s.t0) / _US, 0.01), "pid": self.pid,
                  "tid": 0, "cat": "engine", "args": s.args}
                 for s in self.spans if s is not None]
        return {"traceEvents": meta + spans + self.events,
                "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)


class _SpanCtx:
    __slots__ = ("rec", "name", "args", "ann", "idx", "parent", "t0")

    def __init__(self, rec: TraceRecorder, name: str, args: Dict[str, Any]):
        self.rec, self.name, self.args = rec, name, args

    def __enter__(self):
        rec = self.rec
        self.idx = len(rec.spans)
        self.parent = rec._open[-1] if rec._open else None
        rec.spans.append(None)                 # filled in at the close
        rec._open.append(self.idx)
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.ann.__exit__(*exc)
        rec = self.rec
        rec._open.pop()
        rec.spans[self.idx] = Span(self.name, self.t0, t1, self.parent,
                                   self.args)
        return False


def validate_trace(doc: Dict[str, Any]) -> None:
    """Raise ``ValueError`` unless ``doc`` is structurally valid
    Chrome/Perfetto trace JSON (used by tests and the CLI after write)."""
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("trace document must carry a traceEvents array")
    for i, ev in enumerate(doc["traceEvents"]):
        for key in ("name", "ph", "pid"):
            if key not in ev:
                raise ValueError(f"event {i} missing {key!r}: {ev}")
        ph = ev["ph"]
        if ph not in ("X", "i", "B", "E", "M", "C"):
            raise ValueError(f"event {i} has unknown phase {ph!r}")
        if ph == "X" and ("ts" not in ev or "dur" not in ev):
            raise ValueError(f"complete event {i} missing ts/dur: {ev}")
        if ph in ("i", "C") and "ts" not in ev:
            raise ValueError(f"event {i} ({ph!r}) missing ts: {ev}")
        if ph == "C" and not ev.get("args"):
            raise ValueError(f"counter event {i} has no series args: {ev}")
