"""Mesh-parallel diffusion serving: the multi-device runtime layer over
``DiffusionServingEngine``.

``ShardedDiffusionEngine`` places the slot batch on a ``(data, model)``
mesh:

- **slots over data** — the latent batch (S, H, W, C) and every per-slot
  row of the FastCache state (cache payloads, chi^2 sigma trackers, policy
  counters, stat accumulators) shard over the ``data`` axis via the
  ``kind="serve"`` rule set in ``distributed/sharding.py``
  (``serve_state_shardings``);
- **weights over model** — DiT params shard tensor-parallel through the
  same ``param_shardings`` tables the training launcher uses;
- the jitted ``serve_step`` takes **donated** state buffers with explicit
  in/out shardings, so cache state is aliased device-resident step over
  step and never round-trips host memory.

On top sits an **async dispatch loop**: JAX dispatch is already
asynchronous, so the host races ahead of the accelerator as long as nothing
forces a sync.  The two host syncs of the single-device engine are removed:

- *admission*: queue pops, slot assignment and noise generation happen on
  the host while step k is in flight; the noise lands through a per-slot
  ``jax.device_put`` with the slot's shard spec (the x-spec minus the slot
  axis, i.e. the layout of one resident row), and the fused
  ``reset+seed`` admission program is enqueued *behind* step k — double
  buffering: the device always has step k+1's work queued before step k
  retires, and mid-flight admission stays bitwise-invisible to resident
  samples (``CachedDiT._fastcache_mixed_step`` warms the cold rows);
- *completion*: finished slots' latents are captured as device-side row
  copies (enqueued, not fetched); the single blocking device->host
  transfer happens once per ``run()`` after the trace drains.

Because admission decisions depend only on host bookkeeping (slot
occupancy and per-slot step counters), the async loop schedules the exact
same (request, slot, step) trace as the synchronous engine — the sharded
engine is bitwise-identical to ``DiffusionServingEngine`` per policy,
which ``tests/test_sharded_serving.py`` asserts on an 8-virtual-device CPU
mesh (``make test-sharded``).

**Numerics self-check.**  SPMD partitioning is a compiler transform, and a
wrong partition is *silent* — during bring-up on this jax/XLA version the
CPU backend was caught both double-counting a matmul product (weight dims
sharded over ``data`` against batch-over-``data`` activations) and
NaN-ing the serve_step outright on any ``model > 1`` mesh, while every
``model == 1`` topology is bitwise-exact.  The engine therefore runs a
startup self-check whenever the model axis is wider than one device (or
``numerics_check=True``): two synthetic serve_steps on the mesh, compared
leaf-by-leaf against a single-device reference, raising ``RuntimeError``
on NaN or out-of-tolerance drift instead of serving garbage.
``chip_smoke.py --four-chips`` serves the (4, 1) and (2, 2) meshes on four
TPU chips against the single-device engine.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.runner import CachedDiT
from repro.distributed.sharding import (ShardingCtx, make_rules,
                                        param_shardings,
                                        serve_metrics_shardings,
                                        serve_plan_shardings,
                                        serve_snapshot_shardings,
                                        serve_state_shardings, spec_for,
                                        use_sharding)
from repro.launch.mesh import make_serving_mesh
from repro.serving.diffusion_engine import DiffusionServingEngine
from repro.serving.scheduler import DiffusionRequest, RequestQueue


class ShardedDiffusionEngine(DiffusionServingEngine):
    """``DiffusionServingEngine`` on a ``(data, model)`` mesh with an async
    host-admission dispatch loop.  Host orchestration (slots, queue,
    lockstep baseline, stats conventions) is inherited unchanged — the
    subsystem replaces the device runtime underneath it."""

    def __init__(self, runner: CachedDiT, params, *, max_slots: int,
                 mesh: Optional[Mesh] = None, num_steps: int = 50,
                 guidance_scale: float = 4.0, num_train_steps: int = 1000,
                 max_steps: Optional[int] = None,
                 async_admission: bool = True,
                 numerics_check: Optional[bool] = None,
                 cfg_rows: bool = True, collector=None, tracer=None,
                 enable_metrics: bool = True, audit_fraction: float = 0.0,
                 audit_seed: int = 0):
        self.mesh = mesh if mesh is not None else make_serving_mesh()
        self.rules = make_rules("serve")
        self._ctx = ShardingCtx(self.mesh, self.rules)
        self.async_admission = async_admission
        super().__init__(runner, params, max_slots=max_slots,
                         num_steps=num_steps, guidance_scale=guidance_scale,
                         num_train_steps=num_train_steps,
                         max_steps=max_steps, cfg_rows=cfg_rows,
                         collector=collector, tracer=tracer,
                         enable_metrics=enable_metrics,
                         audit_fraction=audit_fraction,
                         audit_seed=audit_seed)
        # default: self-check exactly the regime where the partitioner has
        # been caught miscompiling (a model axis wider than one device);
        # model==1 topologies are covered bitwise by the parity tests
        if numerics_check is None:
            numerics_check = self.topology()["model"] > 1
        if numerics_check:
            self._verify_step_numerics()

    # -- placement + compilation ----------------------------------------

    def _place_and_compile(self) -> None:
        mesh, rules, ctx = self.mesh, self.rules, self._ctx
        rep = NamedSharding(mesh, P())
        # pre-placement params, kept for the numerics self-check's
        # single-device reference engine (a reference, not a copy)
        self._unplaced_params = self.params

        # shardings: weights via the model's ParamDef tree, state via the
        # kind="serve" cache-state tables, latents + sampling-plan tables
        # slot-major over `data`
        self._params_sh = param_shardings(self.runner.model.param_defs(),
                                          ctx)
        # the state walker is policy-agnostic: it derives slot axes from
        # leaf ranks/extents (batch = this engine's state rows, CFG pairs
        # included), never from state keys
        self._state_sh = serve_state_shardings(
            self.state, ctx, batch=self.rows_per_slot * self.S,
            layers=self.runner.L)
        self._plan_sh = serve_plan_shardings(self.plan, ctx)
        self._slot_acc_sh = {
            k: NamedSharding(mesh, spec_for((self.S,), ("slot",), ctx))
            for k in self.slot_acc}
        x_spec = spec_for(self.x.shape, ("slot", None, None, None), ctx)
        self._x_sh = NamedSharding(mesh, x_spec)
        # one slot's row = the x spec minus the slot axis: admission noise
        # lands with this spec so the staged write matches the resident
        # layout (no resharding inside the admission program)
        self._slot_row_sh = NamedSharding(mesh, P(*x_spec[1:]))
        # one slot's plan row likewise: the ts-table spec minus the slot
        # axis — admission plan rows land through the same per-slot
        # device_put mechanism as the noise
        self._plan_row_sh = NamedSharding(
            mesh, P(*self._plan_sh["ts"].spec[1:]))
        self._acc_sh = {k: rep for k in self.acc}
        # metrics plane: per-slot leaves ride the slot shard, counters and
        # histogram bins replicate (serve_metrics_shardings documents why
        # this is a dedicated walker, not the state walker)
        self._metrics_sh = serve_metrics_shardings(self.metrics, ctx)

        self.params = jax.device_put(self.params, self._params_sh)
        self.state = jax.device_put(self.state, self._state_sh)
        self.plan = jax.device_put(self.plan, self._plan_sh)
        self.x = jax.device_put(self.x, self._x_sh)
        self.acc = jax.device_put(self.acc, self._acc_sh)
        self.slot_acc = jax.device_put(self.slot_acc, self._slot_acc_sh)
        self.metrics = jax.device_put(self.metrics, self._metrics_sh)
        # schedule constants ride along replicated so the jitted programs
        # never see mixed device commitments
        self.sched = jax.device_put(self.sched, rep)

        # trace under the serve sharding ctx so `constrain` calls in the
        # model blocks and the fastcache scan carry bind to this mesh
        def step_fn(params, state, x, plan, step_idx, labels, active, acc,
                    slot_acc, metrics, audit_flag):
            with use_sharding(mesh, rules):
                return self._serve_step_impl(params, state, x, plan,
                                             step_idx, labels, active, acc,
                                             slot_acc, metrics, audit_flag)

        def reset_fn(state, rows):
            with use_sharding(mesh, rules):
                return self.runner.reset_slot(state, rows)

        def admit_fn(state, x, plan, slot_acc, rows, slot, noise, ts_row,
                     ts_prev_row, guid):
            with use_sharding(mesh, rules):
                return self._admit_impl(state, x, plan, slot_acc, rows,
                                        slot, noise, ts_row, ts_prev_row,
                                        guid)

        self._step = jax.jit(
            step_fn,
            in_shardings=(self._params_sh, self._state_sh, self._x_sh,
                          self._plan_sh, rep, rep, rep, self._acc_sh,
                          self._slot_acc_sh, self._metrics_sh, rep),
            out_shardings=(self._x_sh, self._state_sh, self._acc_sh,
                           self._slot_acc_sh, self._metrics_sh),
            donate_argnums=(1, 2, 7, 8, 9))
        self._reset = jax.jit(
            reset_fn, in_shardings=(self._state_sh, rep),
            out_shardings=self._state_sh, donate_argnums=(0,))
        self._admit = jax.jit(
            admit_fn,
            in_shardings=(self._state_sh, self._x_sh, self._plan_sh,
                          self._slot_acc_sh, rep, rep, self._slot_row_sh,
                          self._plan_row_sh, self._plan_row_sh, rep),
            out_shardings=(self._state_sh, self._x_sh, self._plan_sh,
                           self._slot_acc_sh),
            donate_argnums=(0, 1, 2, 3))

        # preemption pair (serving/slo/): snapshots come out fully
        # REPLICATED (serve_snapshot_shardings — a snapshot must be
        # restorable into any slot, and under a data-sharded slot batch
        # different slots live on different mesh positions; replicating
        # the single-slot-sized checkpoint makes _restore a plain scatter
        # for every target slot).  The layout is derived structurally via
        # eval_shape so any policy's state snapshot places without edits.
        def snapshot_fn(state, x, plan, slot_acc, rows, slot):
            with use_sharding(mesh, rules):
                return self._snapshot_impl(state, x, plan, slot_acc, rows,
                                           slot)

        def restore_fn(state, x, plan, slot_acc, snap, rows, slot):
            with use_sharding(mesh, rules):
                return self._restore_impl(state, x, plan, slot_acc, snap,
                                          rows, slot)

        snap_struct = jax.eval_shape(
            self._snapshot_impl, self.state, self.x, self.plan,
            self.slot_acc, jnp.zeros((self.rows_per_slot,), jnp.int32),
            jnp.zeros((), jnp.int32))
        self._snap_sh = serve_snapshot_shardings(snap_struct, ctx)
        self._snapshot = jax.jit(
            snapshot_fn,
            in_shardings=(self._state_sh, self._x_sh, self._plan_sh,
                          self._slot_acc_sh, rep, rep),
            out_shardings=self._snap_sh)
        self._restore = jax.jit(
            restore_fn,
            in_shardings=(self._state_sh, self._x_sh, self._plan_sh,
                          self._slot_acc_sh, self._snap_sh, rep, rep),
            out_shardings=(self._state_sh, self._x_sh, self._plan_sh,
                           self._slot_acc_sh),
            donate_argnums=(0, 1, 2, 3))

    # -- async admission / harvest --------------------------------------

    def _staged_noise(self, req: DiffusionRequest) -> jax.Array:
        # per-slot device_put with the slot's shard spec: the transfer is
        # staged while the current step is in flight, and the admission
        # program consumes it without resharding
        return jax.device_put(self.request_noise(req), self._slot_row_sh)

    def _staged_plan(self, ts_row, ts_prev_row):
        # plan rows land through the same per-slot device_put mechanism as
        # the admission noise: staged with one slot's table-row spec while
        # the in-flight step runs, consumed by _admit without resharding
        return (jax.device_put(jnp.asarray(ts_row), self._plan_row_sh),
                jax.device_put(jnp.asarray(ts_prev_row), self._plan_row_sh))

    def _harvest(self, done_slots: List[int]) -> None:
        if not self.async_admission:
            return super()._harvest(done_slots)
        # deferred: enqueue device-side row copies (the donated next step
        # cannot clobber them — the runtime orders the copy before reuse)
        # and materialize once after the trace drains; the
        # engine.harvest.fetch span around this call so times the enqueue
        # only, and the fetch itself falls in finalize_requests
        for s in done_slots:
            self.slots[s].latents = self.x[s]
            self.slots[s].cache = {k: v[s]
                                   for k, v in self.slot_acc.items()}

    def finalize_requests(self, finished: List[DiffusionRequest]) -> None:
        # the drive loop's single sync point (run end — both engine.run
        # and the SLO control plane's loops call it): fetch all deferred
        # latents and request-scoped cache counters
        if not self.async_admission:
            return
        for r in finished:
            if isinstance(r.latents, jax.Array):
                r.latents = np.asarray(r.latents).copy()
            if r.cache is not None:
                r.cache = {k: float(np.asarray(v))
                           for k, v in r.cache.items()}

    # -- numerics self-check --------------------------------------------

    def _verify_step_numerics(self, *, atol: float = 1e-2) -> None:
        """Run two synthetic serve_steps through the compiled SPMD program
        and compare every output leaf against a single-device reference
        engine.  A silently mis-partitioned program (double-counted
        reductions, NaNs — both observed on model>1 CPU meshes during
        bring-up) fails loudly here instead of corrupting served requests.
        Float leaves are compared as whole tensors, ``||got - ref|| <=
        rtol * ||ref|| + atol * sqrt(size)``: a mis-partition moves the
        norm by O(1), while the reduction-order drift of tensor parallelism
        stays at rounding scale even where single bf16 elements are several
        ulps apart.  ``rtol`` follows the model's compute dtype: 1e-2 in
        float32, 1e-1 in bf16, where one DiT-XL/2 forward already sits
        6.4e-2 from a float32 forward (TPU v5e) and a reordered reduction
        may land a comparable distance away.  Int/bool leaves must match
        exactly."""
        rtol = 1e-2 if self.runner.model.cfg.dtype == "float32" else 1e-1
        ref_eng = DiffusionServingEngine(
            self.runner, self._unplaced_params, max_slots=self.S,
            num_steps=self.num_steps, guidance_scale=self.guidance_scale,
            num_train_steps=self.num_train_steps, max_steps=self.max_steps,
            cfg_rows=self.cfg_rows, enable_metrics=bool(self.metrics),
            audit_fraction=self.audit_fraction, audit_seed=self.audit_seed)
        # with the audit plane on, force the flag True so the self-check
        # also exercises the shadow-forward branch under SPMD partitioning
        aflag = jnp.asarray(self._audit_on)
        eff = self.rows_per_slot * self.S    # state rows (CFG pairs or not)
        x0 = jax.random.normal(jax.random.PRNGKey(0), self.x.shape,
                               jnp.float32)
        labels = jnp.zeros((self.S,), jnp.int32)
        active = jnp.ones((self.S,), bool)
        # (state, x, acc, slot_acc, metrics): the step's carried arguments
        carried = (self.runner.init_state(eff), x0, self._zero_acc(),
                   ref_eng._zero_slot_acc(), ref_eng.metrics)
        shardings = (self._state_sh, self._x_sh, self._acc_sh,
                     self._slot_acc_sh, self._metrics_sh)
        # (step, leaf, share of its tolerance) of the float leaf furthest
        # from the reference, for the caller's log
        self.numerics_drift = (0, "", 0.0)
        for step in range(2):
            idx = jnp.full((self.S,), step, jnp.int32)
            # teacher forcing: both programs step from the same inputs, so
            # each step is compared on its own.  Free-running, the second
            # step's discrete choices (fastcache's motion-token top-k, the
            # gates) see inputs one rounding apart and may legitimately
            # differ.  The mesh gets its own copy (both steps donate).
            gst, gx, gacc, gsacc, gm = jax.device_put(
                jax.tree.map(jnp.copy, carried), shardings)
            got = self._step(self.params, gst, gx, self.plan, idx, labels,
                             active, gacc, gsacc, gm, aflag)
            st, x, acc, sacc, m = carried
            rx, rs, racc, rsacc, rm = ref_eng._step(
                ref_eng.params, st, x, ref_eng.plan, idx, labels, active,
                acc, sacc, m, aflag)
            carried = (rs, rx, racc, rsacc, rm)
            for (path, a), b in zip(
                    jax.tree.flatten_with_path(
                        (rx, rs, racc, rsacc, rm))[0],
                    jax.tree.leaves(got)):
                name = jax.tree_util.keystr(path)
                a, b = np.asarray(a), np.asarray(b)
                # jnp's test: numpy does not count bfloat16 as floating
                if jnp.issubdtype(a.dtype, jnp.floating):
                    a, b = a.astype(np.float64), b.astype(np.float64)
                    err = float(np.linalg.norm(a - b))
                    ref_norm = float(np.linalg.norm(a))
                    used = err / (rtol * ref_norm + atol * np.sqrt(a.size))
                    bad = not np.isfinite(b).all() or not used <= 1.0
                    if used >= self.numerics_drift[2]:
                        self.numerics_drift = (step, name, used)
                    detail = (f"||diff||={err:.3e} ||ref||={ref_norm:.3e}"
                              f" nan={bool(np.isnan(b).any())}")
                else:
                    bad = not np.array_equal(a, b)
                    detail = "integer/bool mismatch"
                if bad:
                    topo = self.topology()
                    raise RuntimeError(
                        f"ShardedDiffusionEngine numerics self-check "
                        f"failed on mesh (data={topo['data']}, "
                        f"model={topo['model']}) at step {step}, leaf "
                        f"{name}: {detail}.  The SPMD partitioner "
                        f"miscompiled the serve_step on this backend.  "
                        f"Use a model=1 topology here, or pass "
                        f"numerics_check=False to override.")

    # -- reporting ------------------------------------------------------

    def topology(self) -> Dict[str, int]:
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return {"data": shape.get("data", 1), "model": shape.get("model", 1),
                "devices": int(self.mesh.devices.size)}
