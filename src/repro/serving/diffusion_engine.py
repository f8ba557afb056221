"""Continuous-batching serving engine for DiT sampling with per-slot
FastCache state — the diffusion twin of ``serving/engine.py``'s slot pattern.

The engine owns a fixed batch of ``max_slots`` generation slots.  Each slot
holds one request: its class label, its own **sampling plan** (DDIM step
budget + guidance scale), its own DDIM step index, its CFG pair (cond row
``s`` + uncond row ``S + s`` of the doubled model batch) and its per-slot
cache state inside the shared ``CachedDiT`` state (gate variance trackers,
cache payloads, policy counters — all (batch,)-indexed).  One jitted
``serve_step`` advances every active slot one denoising step; finished
slots emit latents and free immediately; queued requests are admitted into
free slots mid-flight.

**Heterogeneous plans.**  The denoising schedule is per-slot state, not
engine config: the engine keeps device-resident ``(S, max_steps)``
``ts``/``ts_prev`` plan tables plus a per-slot ``(S,)`` guidance vector,
and admission writes the request's plan rows inside the same fused
``_admit`` call that resets the slot's cache state and seeds its latents.
One batch therefore mixes 20-step and 50-step jobs at different guidance
scales; CFG rows are materialized by default, with ``guidance == 1.0``
expressed per-sample by the blend weights (bitwise-equal to an unguided
solo run — see ``sampler.denoise_step``).  Finish detection is per-slot:
slot ``s`` completes after its own ``slot_budget[s]`` steps.

**Static no-CFG fast path.**  ``cfg_rows=False`` opts a
guidance==1.0-only deployment out of the uncond half entirely: slots are
single state rows, the model batch is S instead of 2S (the pre-plan-table
cost for homogeneous unguided traffic), and requests carrying any other
guidance scale are rejected at admission.  Latents stay bitwise-equal to
the default engine at guidance 1.0 (the scalar-1.0 path in
``denoise_step`` statically skips CFG).

**Policy-agnostic state.**  The engine never names cache-state keys: the
policy's state is an opaque pytree (``CachedDiT.init_state``), slot resets
go through ``reset_slot``, and the per-request counters it accumulates are
whatever ``(batch,)`` stat keys the policy's ``stats`` block carries — so
a newly registered cache policy serves without edits here.

Safety of mid-flight admission rests on two properties of ``CachedDiT``:
every cache decision is per-sample (one slot's state never influences a
batchmate's outputs), and a mixed warm/cold batch warms the cold sample up
with a full forward while warm samples keep their gated path — so a request
admitted at engine step k reproduces its solo run from step 0 *under its
own plan*, and resident requests are untouched by the admission.

Headline cache counters accumulate only ACTIVE slots' decisions (idle slots
re-feed frozen latents, trivially skip, and would inflate the ratio) —
matching the ``serving/engine.py`` convention.  A second, request-scoped
per-slot accumulator is zeroed at admission and harvested into
``req.cache`` at completion, so workload analyses (e.g. cache ratio by step
budget) never need a per-step host sync.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.runner import CachedDiT
from repro.diffusion import sampler
from repro.diffusion import schedule as sch
from repro.obs import audit as obs_audit
from repro.obs import metrics as obs_metrics
from repro.obs.metrics import MetricsCollector
from repro.obs.tracing import TraceRecorder, span
from repro.serving.scheduler import (DiffusionRequest, RequestQueue,
                                     SamplingPlan)

F32 = jnp.float32


class DiffusionServingEngine:
    def __init__(self, runner: CachedDiT, params, *, max_slots: int,
                 num_steps: int = 50, guidance_scale: float = 4.0,
                 num_train_steps: int = 1000,
                 max_steps: Optional[int] = None,
                 cfg_rows: bool = True,
                 collector: Optional[MetricsCollector] = None,
                 tracer: Optional[TraceRecorder] = None,
                 enable_metrics: bool = True,
                 audit_fraction: float = 0.0,
                 audit_seed: int = 0):
        # the bitwise admission-invariance contract needs per-sample gating:
        # global mode reduces the chi^2 statistic over the whole batch, so
        # an admission would silently change residents' gate decisions
        if runner.gate_mode != "per_sample":
            raise ValueError(
                "DiffusionServingEngine requires FastCacheConfig("
                f"gate_mode='per_sample'); got {runner.gate_mode!r}")
        # static no-CFG fast path: a deployment that will only ever serve
        # guidance==1.0 opts out of the uncond half entirely — single-row
        # slots, model batch S instead of 2S (requests asking for any other
        # guidance are rejected at resolve_plan)
        if not cfg_rows and guidance_scale != 1.0:
            raise ValueError(
                "cfg_rows=False is the guidance==1.0-only fast path; got "
                f"default guidance_scale={guidance_scale}")
        self.cfg_rows = cfg_rows
        self.rows_per_slot = 2 if cfg_rows else 1
        self.runner = runner
        self.params = params
        self.S = max_slots
        # (num_steps, guidance_scale) is the DEFAULT plan, applied to
        # requests that don't carry their own; max_steps is the plan-table
        # width — the largest per-request step budget this engine admits
        self.num_steps = num_steps
        self.guidance_scale = guidance_scale
        self.default_plan = SamplingPlan(num_steps, guidance_scale)
        self.max_steps = max_steps if max_steps is not None else num_steps
        if self.max_steps < num_steps:
            raise ValueError(f"max_steps={self.max_steps} < default "
                             f"num_steps={num_steps}")
        self.num_train_steps = num_train_steps
        cfg = runner.model.cfg
        self.img = cfg.dit.image_size
        self.ch = cfg.dit.in_channels

        self.sched = sch.linear_schedule(num_train_steps)
        # per-slot plan tables: row s is slot s's padded DDIM schedule (a
        # plan's rows land here inside the fused _admit call); every slot
        # starts on the default plan so idle rows still hold valid indices
        ts_row, prev_row = self.default_plan.rows(self.max_steps,
                                                  num_train_steps)
        self.plan = {
            "ts": jnp.tile(jnp.asarray(ts_row)[None], (max_slots, 1)),
            "ts_prev": jnp.tile(jnp.asarray(prev_row)[None], (max_slots, 1)),
            "guidance": jnp.full((max_slots,), guidance_scale, F32),
        }

        # CFG rows are materialized by default (guidance==1.0 is a
        # per-sample blend weight), so the state batch is fixed at 2S and
        # slots never resize when a different-guidance request lands; the
        # cfg_rows=False fast path drops the uncond half (state batch S)
        self.state = runner.init_state(self.rows_per_slot * max_slots)
        # per-slot counters the engine accumulates are whatever (batch,)
        # stat keys the POLICY's state carries — the engine names none
        self._acc_keys = tuple(k for k, v in self.state["stats"].items()
                               if getattr(v, "ndim", 0) == 1)
        # shadow-compute audit plane (obs/audit.py): on a deterministic
        # seeded fraction of serve steps, the jitted step also runs the
        # full uncached forward and accumulates cached-vs-true error into
        # the metrics pytree + the per-request slot accumulators.  The
        # fraction only picks which host-computed booleans are True — the
        # traced program is identical for every step, so audit-on steady
        # state stays compile-free.
        if not 0.0 <= audit_fraction <= 1.0:
            raise ValueError(f"audit_fraction must be in [0, 1], got "
                             f"{audit_fraction}")
        if audit_fraction > 0.0 and not enable_metrics:
            raise ValueError("audit_fraction > 0 needs the metrics plane; "
                             "enable_metrics=False has nowhere to "
                             "accumulate audit error")
        self.audit_fraction = float(audit_fraction)
        self.audit_seed = int(audit_seed)
        self._audit_on = audit_fraction > 0.0
        self._audit_bound = runner.audit_bound() if self._audit_on else None
        self.x = jnp.zeros((max_slots, self.img, self.img, self.ch), F32)
        self.slots: List[Optional[DiffusionRequest]] = [None] * max_slots
        self.slot_step = np.full((max_slots,), -1, np.int32)
        self.slot_budget = np.full((max_slots,), num_steps, np.int32)
        self.slot_label = np.zeros((max_slots,), np.int32)
        self.clock = 0                      # engine steps taken
        self.model_steps = 0                # steps that actually ran the DiT
        # active-slot-only counters (PR 1 convention), accumulated on-device
        # inside serve_step so the host never syncs per step; slot_acc is
        # the request-scoped view (zeroed at admission, harvested on finish)
        self.acc = self._zero_acc()
        self.slot_acc = self._zero_slot_acc()
        # device-resident metrics plane (obs): counters/histograms updated
        # with pure jnp inside the jitted step (donated like the state) and
        # harvested by the collector only at run end / window close — the
        # zero-sync rule.  enable_metrics=False traces the step without any
        # metric ops ({} is a static-empty pytree), for A/B overhead runs.
        self.collector = collector
        self.tracer = tracer
        self._metrics_on = enable_metrics
        audit_layers = (runner.L + 1) if self._audit_on else None
        self.metrics = (obs_metrics.init_device_metrics(
            max_slots, audit_layers=audit_layers,
            token_metrics=runner.reducer is not None)
            if enable_metrics else {})
        if collector is not None and self._audit_on:
            collector.set_audit_context(bound=self._audit_bound,
                                        fraction=self.audit_fraction)

        self._place_and_compile()

    def _place_and_compile(self) -> None:
        """Jit the engine's device entry points.  State, latents, plan
        tables and the stat accumulators are DONATED: they live in device
        buffers that are aliased step-over-step and never round-trip host
        memory (asserted in tests via buffer deletion + a device-to-host
        transfer guard).  ``ShardedDiffusionEngine`` overrides this to add
        mesh placement and explicit in/out shardings."""
        self._step = jax.jit(self._serve_step_impl,
                             donate_argnums=(1, 2, 7, 8, 9))
        self._reset = jax.jit(self.runner.reset_slot, donate_argnums=(0,))
        self._admit = jax.jit(self._admit_impl, donate_argnums=(0, 1, 2, 3))
        # preemption pair (serving/slo/): _snapshot extracts one slot's rows
        # into fresh buffers (NOT donated — the live state keeps serving),
        # _restore scatters a snapshot back with the same donation set as
        # _admit.  Both take the slot index as a traced scalar, so one
        # executable serves every slot.
        self._snapshot = jax.jit(self._snapshot_impl)
        self._restore = jax.jit(self._restore_impl,
                                donate_argnums=(0, 1, 2, 3))

    def _zero_acc(self) -> Dict[str, jax.Array]:
        return {k: jnp.zeros((), F32) for k in self._acc_keys}

    def _zero_slot_acc(self) -> Dict[str, jax.Array]:
        # with the audit plane on, the per-request error budget rides the
        # same accumulator: zeroed at admission, harvested into req.cache
        keys = self._acc_keys + (obs_audit.AUDIT_ACC_KEYS
                                 if self._audit_on else ())
        return {k: jnp.zeros((self.S,), F32) for k in keys}

    # -- jitted body ----------------------------------------------------

    def _serve_step_impl(self, params, state, x, plan, step_idx, labels,
                         active, acc, slot_acc, metrics, audit_flag):
        """Advance all slots one denoising step.  ``step_idx`` (S,) is each
        slot's position in ITS OWN plan row of the ``(S, max_steps)``
        tables; idle slots (active=False) run through the model as padding
        but their latents are frozen and their cache decisions are excluded
        from the ``acc`` headline counters.  ``audit_flag`` is the
        host-computed () boolean from the audit schedule — traced, so one
        executable serves audited and plain steps alike (always False when
        the audit plane is off; the cond below is then statically dead)."""
        idx = jnp.clip(step_idx, 0, self.max_steps - 1)
        t = jnp.take_along_axis(plan["ts"], idx[:, None], axis=1)[:, 0]
        t_prev = jnp.take_along_axis(plan["ts_prev"], idx[:, None],
                                     axis=1)[:, 0]
        before = state["stats"]
        guidance = plan["guidance"] if self.cfg_rows else 1.0
        # cfg_rows=False is the static no-CFG fast path: a scalar 1.0
        # statically disables guidance inside denoise_step, so the model
        # batch is S (no uncond half) instead of 2S
        if self._audit_on:  # static: the audit plane also needs the eps
            x_new, state, eps = sampler.denoise_step(
                self.runner, params, self.sched, state, x, t, t_prev,
                labels, guidance_scale=guidance, return_eps=True)
        else:
            x_new, state = sampler.denoise_step(
                self.runner, params, self.sched, state, x, t, t_prev,
                labels, guidance_scale=guidance)
        x_new = jnp.where(active[:, None, None, None], x_new, x)
        act_rows = (jnp.concatenate([active, active]) if self.cfg_rows
                    else active)
        delta = {k: (state["stats"][k] - before[k]) * act_rows
                 for k in acc}
        acc = {k: acc[k] + jnp.sum(delta[k]) for k in acc}
        fold = ((lambda d: d[:self.S] + d[self.S:]) if self.cfg_rows
                else (lambda d: d))
        slot_acc = {**slot_acc,
                    **{k: slot_acc[k] + fold(delta[k]) for k in delta}}
        if self._metrics_on:  # static: off traces a metrics-free step
            metrics = self._update_metrics(metrics, active, delta)
        if self._audit_on:  # static: off is a plain cached-only step
            metrics, slot_acc = obs_audit.apply_audit(
                self.runner, params, self.sched, state, x, t, t_prev,
                labels, guidance, active, eps, self.cfg_rows,
                self._audit_bound, metrics, slot_acc, audit_flag)
        return x_new, state, acc, slot_acc, metrics

    def _update_metrics(self, metrics, active, delta):
        """Pure-jnp device-metrics updates folded into the jitted step —
        a handful of fused scalar ops against the full DiT forward.  Keys
        the policy's stats block does not carry are simply not counted."""
        act_f = active.astype(F32)
        n_act = jnp.sum(act_f)
        metrics = obs_metrics.inc(metrics, obs_metrics.SERVE_STEPS, 1.0)
        metrics = obs_metrics.inc(metrics, obs_metrics.ACTIVE_SLOT_STEPS,
                                  n_act)
        for name, key in ((obs_metrics.BLOCKS_COMPUTED, "blocks_computed"),
                          (obs_metrics.BLOCKS_SKIPPED, "blocks_skipped"),
                          (obs_metrics.BLOCKS_RUN, "blocks_run"),
                          (obs_metrics.STEP_REUSES, "steps_reused")):
            if key in delta:
                metrics = obs_metrics.inc(metrics, name,
                                          jnp.sum(delta[key]))
        metrics = obs_metrics.observe(metrics, obs_metrics.ACTIVE_SLOTS,
                                      n_act)
        if "steps_reused" in delta:
            rows = float(self.rows_per_slot)
            frac = jnp.sum(delta["steps_reused"]) / jnp.maximum(
                n_act * rows, 1.0)
            metrics = obs_metrics.observe(metrics,
                                          obs_metrics.SKIP_FRACTION, frac)
        if "tokens_merged" in delta:
            # token-compression stage on (runner.reducer): stats carry the
            # per-row kept/merged token counts; per-slot we accumulate the
            # realized kept/(kept+merged) ratio (idle slots contribute 0)
            fold = ((lambda d: d[:self.S] + d[self.S:]) if self.cfg_rows
                    else (lambda d: d))
            kept, merged = fold(delta["tokens_kept"]), fold(
                delta["tokens_merged"])
            metrics = obs_metrics.inc(metrics, obs_metrics.TOKENS_KEPT,
                                      jnp.sum(delta["tokens_kept"]))
            metrics = obs_metrics.inc(metrics, obs_metrics.TOKENS_MERGED,
                                      jnp.sum(delta["tokens_merged"]))
            ratio = kept / jnp.maximum(kept + merged, 1.0)
            metrics = obs_metrics.slot_add(
                metrics, obs_metrics.SLOT_MERGE_RATIO, ratio)
        return obs_metrics.slot_add(metrics,
                                    obs_metrics.SLOT_ACTIVE_STEPS, act_f)

    def _admit_impl(self, state, x, plan, slot_acc, rows, slot, noise,
                    ts_row, ts_prev_row, guid):
        """Admission writes for one slot, fused into a single donated call:
        reset the slot's gate/cache rows, seed its latents, land its plan
        rows (timestep table rows + guidance scale) and zero its
        request-scoped counters.  Runs as one device program so mid-flight
        admission costs one dispatch and no state copy."""
        state = self.runner.reset_slot(state, rows)
        x = x.at[slot].set(noise)
        plan = {
            "ts": plan["ts"].at[slot].set(ts_row),
            "ts_prev": plan["ts_prev"].at[slot].set(ts_prev_row),
            "guidance": plan["guidance"].at[slot].set(guid),
        }
        slot_acc = {k: v.at[slot].set(0.0) for k, v in slot_acc.items()}
        return state, x, plan, slot_acc

    def _snapshot_impl(self, state, x, plan, slot_acc, rows, slot):
        """Preemption checkpoint for one slot, extracted device-side in a
        single dispatch: the slot's rows of the policy state pytree
        (``snapshot_slot`` — includes ``tokred`` rows when the merge stage
        is on), its latents, its plan-table rows and its request-scoped
        accumulators.  Everything a re-admission needs to resume the
        request bitwise — crucially the ``slot_acc`` row rides along so
        the request's cache counters survive the requeue instead of being
        re-zeroed by ``_admit``."""
        return {
            "state": self.runner.snapshot_slot(state, rows),
            "x": jnp.take(x, slot, axis=0),
            "ts": jnp.take(plan["ts"], slot, axis=0),
            "ts_prev": jnp.take(plan["ts_prev"], slot, axis=0),
            "guidance": jnp.take(plan["guidance"], slot, axis=0),
            "slot_acc": {k: jnp.take(v, slot, axis=0)
                         for k, v in slot_acc.items()},
        }

    def _restore_impl(self, state, x, plan, slot_acc, snap, rows, slot):
        """The donated mirror of ``_admit_impl`` for resumed requests:
        scatter a ``_snapshot_impl`` checkpoint into (possibly different)
        slot ``slot`` — restore the policy-state rows bitwise, land the
        half-denoised latents, the plan rows and the preserved counter
        row.  One device program, bitwise-invisible to resident slots."""
        state = self.runner.restore_slot(state, snap["state"], rows)
        x = x.at[slot].set(snap["x"])
        plan = {
            "ts": plan["ts"].at[slot].set(snap["ts"]),
            "ts_prev": plan["ts_prev"].at[slot].set(snap["ts_prev"]),
            "guidance": plan["guidance"].at[slot].set(snap["guidance"]),
        }
        slot_acc = {k: v.at[slot].set(snap["slot_acc"][k])
                    for k, v in slot_acc.items()}
        return state, x, plan, slot_acc

    # -- host orchestration ---------------------------------------------

    def _slot_rows(self, s: int) -> jnp.ndarray:
        """State rows owned by slot s (the CFG cond/uncond pair, or the
        single cond row on the cfg_rows=False fast path)."""
        if self.cfg_rows:
            return jnp.array([s, self.S + s], jnp.int32)
        return jnp.array([s], jnp.int32)

    def request_noise(self, req: DiffusionRequest) -> jax.Array:
        """The request's deterministic initial latents, (img, img, ch) —
        shared with solo replays (``sample(..., x_init=noise[None])``)."""
        return jax.random.normal(jax.random.PRNGKey(req.seed),
                                 (self.img, self.img, self.ch), F32)

    def free_slots(self) -> List[int]:
        return [s for s in range(self.S) if self.slots[s] is None]

    def reset_clock(self) -> None:
        """Rewind the step clock and headline counters (e.g. after a warm-up
        trace, so a timed trace's absolute arrival steps line up).  Requires
        an idle engine; per-slot raw accumulators keep their history."""
        if any(r is not None for r in self.slots):
            raise ValueError("reset_clock requires an idle engine; slots "
                             f"{[s for s, r in enumerate(self.slots) if r is not None]} "
                             "still hold requests")
        self.clock = 0
        self.model_steps = 0
        self.acc = self._zero_acc()

    def resolve_plan(self, req: DiffusionRequest) -> SamplingPlan:
        """The request's concrete sampling plan: its own
        ``num_steps``/``guidance_scale`` where set, the engine defaults
        otherwise.  The resolved values are written back onto the request
        so a finished request records the exact plan it ran under (solo
        replays read them)."""
        n = req.num_steps if req.num_steps is not None else self.num_steps
        g = (req.guidance_scale if req.guidance_scale is not None
             else self.guidance_scale)
        if n > self.max_steps:
            raise ValueError(
                f"request rid={req.rid} wants num_steps={n} but this "
                f"engine's plan tables are max_steps={self.max_steps} "
                f"wide; construct the engine with max_steps>={n}")
        if not self.cfg_rows and g != 1.0:
            raise ValueError(
                f"request rid={req.rid} wants guidance_scale={g} but this "
                f"engine runs the cfg_rows=False no-CFG fast path "
                f"(guidance==1.0 only; no uncond rows are materialized)")
        req.num_steps, req.guidance_scale = n, float(g)
        return SamplingPlan(n, float(g))

    def _staged_noise(self, req: DiffusionRequest) -> jax.Array:
        """Initial latents staged for an admission write.  The sharded
        engine overrides this to land the noise via ``jax.device_put`` with
        the slot's shard spec (overlapping the in-flight step)."""
        return self.request_noise(req)

    def _staged_plan(self, ts_row: np.ndarray, ts_prev_row: np.ndarray
                     ) -> Tuple[jax.Array, jax.Array]:
        """Plan-table rows staged for an admission write; the sharded
        engine lands them via the same per-slot ``device_put`` mechanism as
        the noise."""
        return jnp.asarray(ts_row), jnp.asarray(ts_prev_row)

    def add_request(self, req: DiffusionRequest) -> bool:
        """Admit one request into a free slot (mid-flight is fine): seed its
        latents, land its plan rows and fully reset the slot's gate/cache
        state — one donated device call, bitwise-invisible to resident
        slots.  A request carrying a preemption snapshot resumes instead:
        its checkpointed rows are scattered into the slot bitwise (any free
        slot, not just the donor), its step index picks up at
        ``steps_done``, and its cache accumulators carry over."""
        free = self.free_slots()
        if not free:
            return False
        s = free[0]
        if req.snapshot is not None:
            return self._resume_request(req, s)
        tr = self.tracer
        with span(tr, "engine.admit", rid=req.rid, slot=s):
            with span(tr, "engine.admit.stage"):
                plan = self.resolve_plan(req)
                ts_row, prev_row = plan.rows(self.max_steps,
                                             self.num_train_steps)
                args = (self._slot_rows(s), jnp.asarray(s, jnp.int32),
                        self._staged_noise(req),
                        *self._staged_plan(ts_row, prev_row),
                        jnp.asarray(plan.guidance_scale, F32))
            with span(tr, "engine.admit.dispatch"):
                self.state, self.x, self.plan, self.slot_acc = self._admit(
                    self.state, self.x, self.plan, self.slot_acc, *args)
            self.slots[s] = req
            self.slot_step[s] = 0
            self.slot_budget[s] = plan.num_steps
            self.slot_label[s] = req.label
            req.admit_step = self.clock
            req.queue_wait_steps = max(self.clock - req.arrival_step, 0)
            if self.collector is not None:
                self.collector.inc(obs_metrics.ADMISSIONS)
                self.collector.observe(obs_metrics.QUEUE_WAIT,
                                       req.queue_wait_steps)
            if tr is not None:
                tr.admit(req.rid, s, label=req.label,
                         num_steps=plan.num_steps, engine_step=self.clock)
        return True

    def _resume_request(self, req: DiffusionRequest, s: int) -> bool:
        """Re-admit a preempted request from its device-side snapshot into
        free slot ``s``.  The snapshot is consumed; the request's plan was
        resolved at first admission, so no re-resolution (and no shedding
        re-scaling) happens here — the resumed run must replay the original
        plan bitwise."""
        snap, req.snapshot = req.snapshot, None
        with span(self.tracer, "engine.resume", rid=req.rid):
            self.state, self.x, self.plan, self.slot_acc = self._restore(
                self.state, self.x, self.plan, self.slot_acc, snap,
                self._slot_rows(s), jnp.asarray(s, jnp.int32))
        self.slots[s] = req
        self.slot_step[s] = req.steps_done
        self.slot_budget[s] = req.num_steps
        self.slot_label[s] = req.label
        if self.collector is not None:
            self.collector.inc(obs_metrics.RESUMES)
        if self.tracer is not None:
            self.tracer.admit(req.rid, s, label=req.label,
                              num_steps=req.num_steps,
                              engine_step=self.clock)
        return True

    def preempt(self, s: int) -> DiffusionRequest:
        """Checkpoint slot ``s``'s in-flight request out of the engine: a
        device-side row snapshot (policy-state rows incl. ``tokred``,
        latents, plan rows, request-scoped accumulators) lands on the
        request, the slot frees immediately, and the caller requeues the
        request for later ``add_request`` re-admission — which resumes it
        bitwise.  No host round-trip: the snapshot stays in device
        buffers."""
        req = self.slots[s]
        if req is None:
            raise ValueError(f"preempt: slot {s} holds no request")
        with span(self.tracer, "engine.preempt", rid=req.rid):
            req.snapshot = self._snapshot(self.state, self.x, self.plan,
                                          self.slot_acc, self._slot_rows(s),
                                          jnp.asarray(s, jnp.int32))
            # same convention as completion-free: a freed slot never
            # carries stale gate/cache state
            self.state = self._reset(self.state, self._slot_rows(s))
        req.steps_done = int(self.slot_step[s])
        req.preemptions += 1
        self.slots[s] = None
        self.slot_step[s] = -1
        if self.collector is not None:
            self.collector.inc(obs_metrics.PREEMPTIONS)
        if self.tracer is not None:
            self.tracer.finish(req.rid, engine_step=self.clock)
        return req

    def step(self) -> List[DiffusionRequest]:
        """One engine step: advance all active slots one denoising step.
        Returns the requests that finished on this step (slots freed) —
        each after its OWN plan's step budget.  With a tracer attached the
        step is the ``engine.step`` span; its ``engine.step.dispatch``
        child times the serve step's enqueue only (dispatch is
        asynchronous), and a completion step's wait for the device falls in
        ``engine.harvest.fetch``."""
        active = np.array([r is not None for r in self.slots])
        self.clock += 1
        if not active.any():            # idle tick: time passes, no compute
            return []
        tr = self.tracer
        with span(tr, "engine.step", engine_step=self.clock,
                  active=int(active.sum())):
            with span(tr, "engine.step.prepare"):
                # the audit schedule is a host-side hash of the model-step
                # counter: the jit only ever sees the resulting traced ()
                # boolean, so the sampled schedule never recompiles (and is
                # False forever when the audit plane is off)
                audit_now = self._audit_on and obs_audit.audit_mask(
                    self.model_steps, self.audit_fraction, self.audit_seed)
                args = (jnp.asarray(np.where(active, self.slot_step,
                                             0).astype(np.int32)),
                        jnp.asarray(self.slot_label), jnp.asarray(active))
                aflag = jnp.asarray(audit_now)
            with span(tr, "engine.step.dispatch"):
                (self.x, self.state, self.acc, self.slot_acc,
                 self.metrics) = self._step(
                    self.params, self.state, self.x, self.plan, *args,
                    self.acc, self.slot_acc, self.metrics, aflag)
            if tr is not None:
                tr.snapshot_slots(self.clock, active, self.slot_acc)
            self.model_steps += 1

            done_slots = []
            for s in np.flatnonzero(active):
                self.slot_step[s] += 1
                if self.slot_step[s] >= self.slot_budget[s]:
                    done_slots.append(int(s))
            if not done_slots:
                return []
            with span(tr, "engine.harvest",
                      rids=[self.slots[s].rid for s in done_slots]):
                return self._finish(done_slots)

    def _finish(self, done_slots: List[int]) -> List[DiffusionRequest]:
        """Harvest and free the slots whose requests ended this step."""
        tr = self.tracer
        with span(tr, "engine.harvest.fetch"):
            self._harvest(done_slots)
        finished: List[DiffusionRequest] = []
        for s in done_slots:
            req = self.slots[s]
            req.finish_step = self.clock
            req.done = True
            if req.cache is not None:
                # control-plane accounting rides the harvested counters
                # (plain host floats — the sharded engine's deferred
                # materialization passes them through unchanged)
                req.cache["queue_wait_steps"] = float(
                    max(req.queue_wait_steps, 0))
                req.cache["preemptions"] = float(req.preemptions)
            if self.collector is not None:
                self.collector.inc(obs_metrics.REQUESTS_FINISHED)
                self.collector.observe(obs_metrics.REQUEST_LATENCY,
                                       req.finish_step - req.arrival_step)
                if (req.deadline_step is not None
                        and req.finish_step > req.deadline_step):
                    self.collector.inc(obs_metrics.DEADLINE_MISSES)
            if tr is not None:
                tr.finish(req.rid, engine_step=self.clock)
            finished.append(req)
            # free immediately: a freed slot is reset below as well as on
            # admission, so it never carries stale gate/cache state
            self.slots[s] = None
            self.slot_step[s] = -1
        # (the reset leaves the padding row cold, so the next step pays one
        # mixed warm-up; a stale-cache-free slot table is worth that
        # once-per-completion cost)
        with span(tr, "engine.harvest.reset"):
            for s in done_slots:
                self.state = self._reset(self.state, self._slot_rows(s))
        return finished

    def _harvest(self, done_slots: List[int]) -> None:
        """Fill ``req.latents`` and ``req.cache`` (the request-scoped cache
        counters) for finished slots.  Synchronous by default (one blocking
        device->host fetch per completion step); the async sharded engine
        overrides this with deferred device-side copies so the dispatch
        loop never blocks on the in-flight step."""
        x_host = np.asarray(self.x)
        acc_host = {k: np.asarray(v) for k, v in self.slot_acc.items()}
        for s in done_slots:
            req = self.slots[s]
            req.latents = x_host[s].copy()
            req.cache = {k: float(v[s]) for k, v in acc_host.items()}

    def run(self, requests: Union[List[DiffusionRequest], RequestQueue],
            *, lockstep: bool = False, sched_policy: str = "fifo",
            max_engine_steps: int = 100_000) -> List[DiffusionRequest]:
        """Drive a whole trace.  ``lockstep=False`` (continuous batching)
        admits arrived requests into free slots every step; ``lockstep=True``
        is the fixed-batch baseline — a new wave is admitted only once every
        slot is free (the classic ``sample()``-per-batch serving pattern).
        ``sched_policy`` ("fifo" or "sjf") picks the admission order among
        arrived requests when ``requests`` is a plain list; pass a
        ``RequestQueue`` to control the policy yourself."""
        queue = (requests if isinstance(requests, RequestQueue)
                 else RequestQueue(list(requests), policy=sched_policy))
        finished: List[DiffusionRequest] = []
        window = (self.collector.window_steps
                  if self.collector is not None else None)
        while (queue or any(r is not None for r in self.slots)):
            if self.clock >= max_engine_steps:
                break
            if not lockstep or all(r is None for r in self.slots):
                while (len(self.free_slots())
                       and queue.peek_arrived(self.clock)):
                    self.add_request(queue.pop_arrived(self.clock))
            finished.extend(self.step())
            if window and self.clock % window == 0:
                # periodic window close: a sanctioned sync point (the only
                # one besides run end) — fetches the small metrics pytree
                self.harvest_metrics()
        if self.collector is not None:
            self.harvest_metrics()      # run end: the standing sync point
        self.finalize_requests(finished)
        return finished

    def finalize_requests(self, finished: List[DiffusionRequest]) -> None:
        """End-of-drive hook for whoever owns the loop (``run`` here, the
        SLO control plane's ``SLOScheduler.run``/``ReplicaRouter.run``
        otherwise): materialize anything a finished request still holds as
        device references.  No-op for this engine (``_harvest`` is already
        synchronous); the async sharded engine overrides it with its
        single end-of-run sync."""

    # -- stats ----------------------------------------------------------

    def harvest_metrics(self) -> Optional[Dict]:
        """Materialize the device metrics pytree into the collector — THE
        metrics sync point.  Called at run end and at periodic window
        closes; never from the per-step path (reprolint's obs-discipline
        check proves harvest is unreachable from any jit region)."""
        if self.collector is None:
            return None
        return self.collector.harvest(self.metrics or None,
                                      at_step=self.clock)

    def cache_stats(self) -> Dict:
        """Engine-lifetime cache counters under the active-slots-only
        convention; raw per-slot (batch,) accumulators — which include idle
        padding steps — under per_slot_*.  Tolerant of any policy's stats
        pytree: counters a policy does not carry report 0.0."""
        def acc(k):
            return float(self.acc.get(k, 0.0))

        def per_slot(k):
            v = self.state["stats"].get(k)
            rows = self.rows_per_slot * self.S
            return ([0.0] * rows if v is None
                    else [float(x) for x in np.asarray(v)])

        skipped, computed = acc("blocks_skipped"), acc("blocks_computed")
        tot = computed + skipped
        return {
            "policy": self.runner.policy,
            "engine_steps": self.clock,
            "model_steps": self.model_steps,
            "blocks_skipped": skipped,
            "blocks_computed": computed,
            "block_cache_ratio": skipped / tot if tot else 0.0,
            "blocks_run": acc("blocks_run"),
            "steps_reused": acc("steps_reused"),
            "per_slot_blocks_skipped": per_slot("blocks_skipped"),
            "per_slot_blocks_computed": per_slot("blocks_computed"),
        }
