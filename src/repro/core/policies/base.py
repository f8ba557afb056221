"""The CachePolicy plugin protocol, registry, and shared machinery.

A *cache policy* is one method for skipping DiT compute across denoising
steps (the paper's FastCache, or one of the baselines it compares against).
Each policy lives in its own module under ``core/policies/``, registers
itself by name, and owns a **minimal, policy-specific state pytree** — a
dict of arrays whose batch rows are the serving slots.  ``CachedDiT``
(core/runner.py) is a thin shell that resolves a policy from the registry
and forwards to it; the serving engines and the sharding walker treat the
state as an opaque pytree, so a new policy module is the ONLY file a new
cache method needs.

Protocol (all four methods must be jit-compatible):

  init_state(batch) -> dict
      Allocate the policy's state for ``batch`` samples.  Only this
      policy's buffers — plus the standard ``stats`` block (see
      ``init_stats``) that the engines and ``summarize_stats`` consume.
  reset_rows(state, rows) -> dict
      Re-arm the given sample rows (an int or index array — e.g. a serving
      slot's CFG cond/uncond pair) for a new request without disturbing
      batchmates.  Stats stay cumulative (engine-lifetime counters).
  snapshot_rows(state, rows) -> dict
      The preemption half of the contract: extract the given sample rows
      into a same-treedef pytree (per-slot leaves row-sliced, replicated
      leaves passed through) — what the serving engines checkpoint when a
      half-denoised request is preempted.  The generic base implementation
      walks the state with the sharding walker's ``_slot_axis`` rank rule,
      so policies only override it when their state breaks that rule.
  restore_rows(state, snap, rows) -> dict
      Scatter a snapshot back into the given rows of a live state —
      re-admission after requeue rarely lands in the donor slot, so
      ``rows`` at restore time may differ from the snapshot's.  Must be
      bitwise: ``restore_rows(state, snapshot_rows(state, rows), rows)``
      is the identity (reprolint's policy-contract check enforces treedef/
      shape/dtype preservation plus this round-trip).  Replicated leaves
      keep the LIVE value — engine-global scalars are not rewound.
  step(params, state, x_in, c) -> (eps, state)
      One denoising-model evaluation: ``x_in`` (B, N, D) are the patch
      tokens, ``c`` the per-sample conditioning.  Every data-dependent
      cache decision must be per-sample ((B,) gates + ``jnp.where``
      masking) so one sample never disturbs a batchmate — the serving
      engines' bitwise mid-flight-admission contract rests on this.
  stats(state) -> dict
      Host-side summary; the default forwards to ``summarize_stats``.

State-pytree contract with the engines / sharding walker:

  - the sample-batch dim is either the LEADING axis of a leaf, or — for
    layer-stacked trackers — axis 1 behind a leading axis of extent
    ``num_layers`` or ``num_layers + 1`` (``serve_state_specs`` in
    distributed/sharding.py uses exactly this rank rule to shard slot rows
    over the mesh ``data`` axis; anything else replicates);
  - ``state["stats"]`` holds per-sample ``(B,)`` float32 counters; every
    key present is accumulated per-request by the serving engines.  The
    standard keys are ``blocks_computed / blocks_skipped / steps_reused /
    motion_frac_sum / blocks_run`` plus the scalar ``steps`` (bumped by the
    ``CachedDiT`` shell, not by policies);
  - arrays only — the engines donate the whole pytree buffer-for-buffer;
  - ``tokred`` is RESERVED: when the token-compression stage is on,
    ``CachedDiT`` rides the TokenReducer's per-sample rows (previous
    full-resolution tokens + warm flag; core/token_reduce.py) under that
    key of the same state dict — policies must pass unknown keys through
    untouched (every ``dict(state)`` copy-through does), and the stats
    block gains the (B,) ``tokens_kept / tokens_merged`` counters.

Registering:

    from repro.core.policies.base import CachePolicy, register

    @register("mycache")
    class MyCache(CachePolicy):
        ...

Import the module from ``core/policies/__init__.py`` (registration import
order defines the ``POLICIES`` tuple order).  Constructor knobs arrive via
``CachedDiT(..., **policy_kwargs)``; every policy receives the full kwarg
set and keeps what it knows (unknown keys are ignored, so policies can
coexist without sharing a signature).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from repro.core import statcache
from repro.distributed.sharding import _slot_axis
from repro.models.dit import DiTModel

F32 = jnp.float32

# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

_REGISTRY: Dict[str, Type["CachePolicy"]] = {}


def register(name: str) -> Callable[[Type["CachePolicy"]],
                                    Type["CachePolicy"]]:
    """Class decorator: register a CachePolicy under ``name``."""
    def deco(cls: Type["CachePolicy"]) -> Type["CachePolicy"]:
        if name in _REGISTRY and _REGISTRY[name] is not cls:
            raise ValueError(f"cache policy {name!r} already registered "
                             f"({_REGISTRY[name].__qualname__})")
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def registered_policies() -> Tuple[str, ...]:
    """Names of all registered policies, in registration order.  This IS
    the source of ``repro.core.POLICIES`` — the tuple cannot drift from the
    registry because it is derived from it on access."""
    return tuple(_REGISTRY)


def get_policy_class(name: str) -> Type["CachePolicy"]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown cache policy {name!r}; registered policies: "
            f"{', '.join(registered_policies()) or '(none)'}") from None


# --------------------------------------------------------------------------
# Base class: shared DiT plumbing + the step-level masked-step helper
# --------------------------------------------------------------------------

class CachePolicy:
    """Base class for cache policies.  Holds the host model and FastCache
    config and provides the shared forward/eps/statistics helpers; see the
    module docstring for the protocol and the state-pytree contract."""

    name: str = ""

    def __init__(self, model: DiTModel, fc, fc_params, *,
                 gate_mode: str = "per_sample", use_fused: bool = False,
                 token_reducer=None, **_unused):
        self.model = model
        self.fc = fc
        self.fc_params = fc_params
        self.gate_mode = gate_mode
        self.use_fused = use_fused
        self.L = model.cfg.num_layers
        # token-compression stage (core/token_reduce.py): when CachedDiT
        # hands a reducer in, the policy's whole transformer path runs on
        # the statically reduced grid — policies size their token-axis
        # buffers with ``self.n_tokens`` and everything else composes
        # untouched (``_eps`` unmerges back to full resolution, so cached
        # eps / image-space buffers never see the reduced grid)
        self.reducer = token_reducer
        self.n_tokens = (token_reducer.reduced_tokens
                         if token_reducer is not None else model.num_tokens)

    # -- protocol ------------------------------------------------------

    def init_state(self, batch: int) -> Dict:
        raise NotImplementedError

    def reset_rows(self, state: Dict, rows) -> Dict:
        """Default: nothing policy-specific to re-arm (stateless policies
        like nocache/l2c)."""
        return dict(state)

    def snapshot_rows(self, state: Dict, rows) -> Dict:
        """Extract ``rows`` into a same-treedef snapshot (the preemption
        checkpoint).  Generic: every leaf whose shape carries the sample
        batch under the ``_slot_axis`` rank rule is row-sliced along that
        axis; replicated leaves (the scalar ``steps``, global trackers)
        pass through so the treedef — which the engines' jitted restore
        programs are traced against — never changes shape."""
        batch = self._state_batch(state)

        def take(leaf):
            axis = _slot_axis(jnp.shape(leaf), batch, self.L)
            return leaf if axis is None else jnp.take(leaf, rows, axis=axis)

        return jax.tree.map(take, state)

    def restore_rows(self, state: Dict, snap: Dict, rows) -> Dict:
        """Scatter a ``snapshot_rows`` pytree back into ``rows`` of a live
        state.  Per-slot leaves are written bitwise; replicated leaves keep
        the LIVE value (engine-global scalars like ``stats["steps"]`` are
        not rewound to preemption time — they are engine-lifetime, not
        request-scoped)."""
        batch = self._state_batch(state)

        def put(leaf, sleaf):
            axis = _slot_axis(jnp.shape(leaf), batch, self.L)
            if axis is None:
                return leaf
            if axis == 0:
                return leaf.at[rows].set(sleaf)
            return leaf.at[:, rows].set(sleaf)

        return jax.tree.map(put, state, snap)

    def step(self, params, state: Dict, x_in: jax.Array, c
             ) -> Tuple[jax.Array, Dict]:
        raise NotImplementedError

    def stats(self, state: Dict) -> Dict[str, float]:
        return summarize_stats(state)

    # -- shared state pieces -------------------------------------------

    def init_stats(self, batch: int) -> Dict[str, jax.Array]:
        """The standard per-sample stat accumulators every policy carries
        (the serving engines accumulate every (B,) key per request).
        ``blocks_computed``/``blocks_skipped`` count a row's own cache
        decisions; ``blocks_run`` counts the DiT blocks the device executed
        over the row's tokens, which is more wherever a block runs batch-wide
        for one row that needs it (the excess is work thrown away).  With
        an active TokenReducer the merge stage's token counters join the
        set — (B,) like every stat key, so the engines' per-request
        accumulation and the obs token counters pick them up with no
        policy or engine edits."""
        out = {
            "blocks_computed": jnp.zeros((batch,), F32),
            "blocks_skipped": jnp.zeros((batch,), F32),
            "steps_reused": jnp.zeros((batch,), F32),
            "motion_frac_sum": jnp.zeros((batch,), F32),
            "blocks_run": jnp.zeros((batch,), F32),
            "steps": jnp.zeros((), F32),
        }
        if self.reducer is not None:
            out["tokens_kept"] = jnp.zeros((batch,), F32)
            out["tokens_merged"] = jnp.zeros((batch,), F32)
        return out

    def _state_batch(self, state: Dict) -> int:
        """The state's sample-row count, read off the mandatory ``stats``
        block (its (B,) per-sample counters are part of the contract) —
        the anchor the generic snapshot/restore walkers classify every
        other leaf against."""
        for k, v in state.get("stats", {}).items():
            if k != "steps" and jnp.ndim(v) == 1:
                return int(jnp.shape(v)[0])
        raise ValueError(
            f"policy {self.name or type(self).__name__!r}: state carries no "
            "(B,) stats counter to infer the sample batch from — override "
            "snapshot_rows/restore_rows or add a per-sample stats key")

    def _state_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.model.cfg.dtype)

    def _eps_shape(self, batch: int) -> Tuple[int, ...]:
        cfg = self.model.cfg
        img = cfg.dit.image_size
        return (batch, img, img, cfg.dit.in_channels)

    # -- shared forward helpers ----------------------------------------

    def _full_forward(self, params, x, c):
        """Full block-stack forward.  Returns ``(x_out, inputs)`` where
        ``inputs`` (L, B, N, D) stacks each block's input (``inputs[l]`` is
        block l's input; block l's output is ``inputs[l+1]``, and the final
        output is ``x_out``)."""
        def body(x, bp):
            return self.model.block_apply(bp, x, c), x

        x_out, inputs = jax.lax.scan(body, x, params["blocks"])
        return x_out, inputs

    def _eps(self, params, hidden_final, c) -> jax.Array:
        # token-compression unmerge: a reduced-grid hidden (the cached
        # path under an active TokenReducer) is scattered back to full
        # resolution before the final layer; full-resolution hiddens
        # (merge off, or the audit plane's shadow forward) pass through —
        # the dispatch is on static shape, so both cases stay one trace
        # each with no runtime branching
        if (self.reducer is not None
                and hidden_final.shape[-2] != self.model.num_tokens):
            hidden_final = self.reducer.unmerge(hidden_final)
        out = self.model.final_layer(params, hidden_final, c)
        p = self.model.cfg.dit.patch_size
        from repro.models.common import unpatchify
        return unpatchify(out[..., :self.model.patch_dim], p,
                          self.model.grid)

    # -- audit plane (obs.audit) ---------------------------------------

    def audit_forward(self, params, x_in: jax.Array, c
                      ) -> Tuple[jax.Array, jax.Array]:
        """The full-forward twin the shadow-compute audit plane runs
        alongside the cached path: an uncached evaluation of the SAME
        inputs, returning ``(eps_true, hidden)`` where ``hidden``
        (L+1, B, N, D) stacks each block's input plus the final hidden —
        the layout ``audit_hidden`` mirrors, so per-layer cached-vs-true
        errors compare like with like.  Stateless and side-effect-free:
        it must never touch the policy's cache payloads or counters."""
        x_out, inputs = self._full_forward(params, x_in, c)
        hidden = jnp.concatenate([inputs, x_out[None]], axis=0)
        return self._eps(params, x_out, c), hidden

    def audit_hidden(self, state: Dict) -> Optional[jax.Array]:
        """The per-layer hidden stack the cached path produced this step,
        (L+1, B, N, D) in ``audit_forward``'s layout — or None when the
        policy keeps no such payload (step-level policies cache eps, not
        hiddens).  None statically disables the audit plane's per-layer
        error accumulation for this policy; end-to-end eps error is
        always audited."""
        return None

    def predicted_error_bound(self) -> Optional[float]:
        """The per-step relative approximation error this policy claims
        for its cached outputs, or None for policies that make no bound
        claim (None never trips ``bound_violations_total``).  FastCache
        derives it from the chi^2 gate (Eq. 9); see ``core/chi2.py``."""
        return None

    def _rel_change(self, x: jax.Array, prev: jax.Array) -> jax.Array:
        """Per-sample relative Frobenius change, (B,).  In global mode the
        statistic is reduced over the batch and broadcast."""
        diff, prevsq = statcache.delta_stats_per_sample(x, prev)
        if self.gate_mode == "global":
            rel = jnp.sqrt(jnp.sum(diff)
                           / jnp.maximum(jnp.sum(prevsq), 1e-12))
            return jnp.broadcast_to(rel, diff.shape)
        return jnp.sqrt(diff / jnp.maximum(prevsq, 1e-12))

    # -- step-level gate core ------------------------------------------

    def masked_step(self, params, state: Dict, x_in: jax.Array, c,
                    skip: jax.Array, *, computed_on_skip: float = 0.0,
                    store: Optional[Callable] = None
                    ) -> Tuple[jax.Array, Dict]:
        """One step under a per-sample step-level gate, for policies that
        reuse the previous step's model output (``state["prev_eps"]``).
        ``skip`` (B,) bool: True reuses that sample's cached eps and leaves
        its cache payload untouched; False recomputes and refreshes it.
        The block stack only runs when at least one sample recomputes.
        ``computed_on_skip`` counts probe blocks (fbcache's block 0)
        charged to skipped samples; the probe runs for every sample, so
        ``blocks_run`` counts it for each, plus the whole stack for each
        when any sample recomputes.  ``store(out, st, inputs, x_out)``
        writes the policy's own payloads into the ``out`` state dict on the
        recompute path (must mask with ``skip`` itself)."""
        def reuse_all(st):
            return st["prev_eps"].astype(F32).astype(x_in.dtype), dict(st)

        def mixed(st):
            x_out, inputs = self._full_forward(params, x_in, c)
            eps = self._eps(params, x_out, c)
            out = dict(st)
            if store is not None:
                store(out, st, inputs, x_out)
            eps_sel = jnp.where(skip[:, None, None, None],
                                st["prev_eps"].astype(eps.dtype), eps)
            out["prev_eps"] = eps_sel.astype(st["prev_eps"].dtype)
            return eps_sel, out

        eps, st = jax.lax.cond(jnp.all(skip), reuse_all, mixed, state)
        st["have_cache"] = jnp.ones_like(state["have_cache"])
        skf = skip.astype(F32)
        stats = dict(st["stats"])
        stats["blocks_computed"] = (stats["blocks_computed"]
                                    + (1.0 - skf) * self.L
                                    + skf * computed_on_skip)
        stats["blocks_skipped"] = (stats["blocks_skipped"]
                                   + skf * (self.L - computed_on_skip))
        stats["steps_reused"] = stats["steps_reused"] + skf
        stats["motion_frac_sum"] = stats["motion_frac_sum"] + (1.0 - skf)
        stats["blocks_run"] = (stats["blocks_run"] + computed_on_skip
                               + jnp.where(jnp.all(skip), 0.0, self.L))
        st["stats"] = stats
        return eps, st


# --------------------------------------------------------------------------
# Host-side stats summary (tolerant: any policy's stats pytree)
# --------------------------------------------------------------------------

def summarize_stats(state) -> Dict[str, float]:
    """Batch-mean view of the (batch,) per-sample accumulators, so the
    reported numbers stay in per-sample units (steps reused per sample,
    blocks skipped per sample, ...) regardless of batch size.  The raw
    per-sample counts are under ``per_sample``.

    Tolerant of any policy's state pytree: counters a policy does not
    carry read as 0.0 rather than raising (the plugin API makes the stats
    block policy-owned; only the keys a policy tracks exist)."""
    s = state.get("stats", {})

    def mean(k):
        v = s.get(k)
        return 0.0 if v is None else float(jnp.mean(jnp.asarray(v, F32)))

    steps = float(s.get("steps", 0.0))
    computed = mean("blocks_computed")
    skipped = mean("blocks_skipped")
    reused = mean("steps_reused")
    total = computed + skipped
    out = {
        "steps": steps,
        "steps_reused": reused,
        "blocks_computed": computed,
        "blocks_skipped": skipped,
        "block_cache_ratio": skipped / total if total else 0.0,
        "mean_motion_fraction": (mean("motion_frac_sum")
                                 / max(1.0, steps - reused)),
    }
    per_sample_keys = [k for k in ("blocks_computed", "blocks_skipped",
                                   "steps_reused", "motion_frac_sum")
                       if jnp.ndim(s.get(k, 0.0))]
    if per_sample_keys:
        out["per_sample"] = {
            k: [float(v) for v in jnp.asarray(s[k])]
            for k in per_sample_keys}
    return out
