"""fastcache: the paper's method (Alg. 1) — STR token partition + per-block
chi^2 statistical gate + learnable linear approximation + motion-aware
blending, with per-sample block gates.

State: the previous step's token embeddings (Eq. 1 saliency reference),
the full per-block input-hidden stack (H_{t-1,l-1} of Eq. 4 — the cache
payload the linear approximators blend against), the chi^2 sliding-window
variance trackers, and the warm-up flag.  No cached eps: fastcache gates
per-block, never per-step.

The payload stack, (L+1, B, N, D), is updated in place.  A gated step
reads it only at the motion tokens (each layer gathered once) and writes
only there: block l's input over layer l where the tau_s gate kept the
token, then the reassembled final hidden over layer L.  Every other token
keeps last step's value, which it already holds.  The step relies on the
serving engine donating the state: the scan writes into the donated
buffer, and without the donation XLA copies the stack once per step.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.core import chi2, linear_approx, saliency, statcache
from repro.core.policies.base import F32, CachePolicy, register
from repro.distributed.sharding import constrain
from repro.kernels import ops as kernel_ops
from repro.kernels import ref as kernel_ref


@register("fastcache")
class FastCache(CachePolicy):
    def __init__(self, model, fc, fc_params, **kw):
        super().__init__(model, fc, fc_params, **kw)
        n = self.n_tokens      # reduced grid when token compression is on
        self.capacity = max(1, int(round(fc.motion_capacity * n)))

    def init_state(self, batch: int) -> Dict:
        m = self.model
        n, d = self.n_tokens, m.cfg.d_model
        dt = self._state_dtype()
        return {
            "prev_tokens_in": jnp.zeros((batch, n, d), dt),
            "prev_hidden": jnp.zeros((self.L + 1, batch, n, d), dt),
            "gate": statcache.init_gate_state(self.L, batch),
            "have_cache": jnp.zeros((batch,), bool),
            "stats": self.init_stats(batch),
        }

    def reset_rows(self, state, rows):
        st = dict(state)
        st["prev_tokens_in"] = state["prev_tokens_in"].at[rows].set(0.0)
        st["prev_hidden"] = state["prev_hidden"].at[:, rows].set(0.0)
        st["gate"] = statcache.reset_gate_slot(state["gate"], rows)
        st["have_cache"] = state["have_cache"].at[rows].set(False)
        return st

    # -- audit plane ---------------------------------------------------

    def audit_hidden(self, state):
        """After ``step``, ``prev_hidden`` IS this step's hidden stack —
        block inputs plus the reassembled final hidden, in exactly
        ``audit_forward``'s (L+1, B, N, D) layout — so the audit plane can
        compare it against the true stack layer by layer."""
        return state["prev_hidden"]

    def predicted_error_bound(self):
        """Eq. 9 bound from the chi^2 gate: the per-step relative error the
        hypothesis test guarantees for a cached block, with the df the gate
        actually uses (motion capacity x d_model — one sample's observed
        elements, matching ``nd`` in ``_gated_step``)."""
        nd = self.capacity * self.model.cfg.d_model
        return chi2.error_bound(self.fc.alpha, nd)

    # ------------------------------------------------------------------

    def step(self, params, state, x_in, c):
        # Per-block gating needs a sample's cache payload.  All-warm
        # batches take the pure gated path; all-cold batches (the first
        # sampling step) take one full forward.  A MIXED batch — a request
        # admitted into a running serving batch — warms up the cold
        # samples with a full forward while the warm samples keep their
        # per-sample gate decisions, cache payloads and trackers (their
        # outputs and state match an admission-free run exactly).
        have = state["have_cache"]
        return jax.lax.cond(
            jnp.all(have),
            lambda s: self._gated_step(params, s, x_in, c),
            lambda s: jax.lax.cond(
                jnp.any(have),
                lambda s2: self._mixed_step(params, s2, x_in, c, have),
                lambda s2: self._cold_step(params, s2, x_in, c),
                s),
            state)

    def _cold_step(self, params, state, x_in, c):
        """Warm-up: one full forward installing the cache payload (the STR
        static bypass is only valid against a real payload)."""
        with jax.named_scope("fastcache.full_forward"):
            x_out, inputs = self._full_forward(params, x_in, c)
        hidden = jnp.concatenate([inputs, x_out[None]], axis=0)
        eps = self._eps(params, x_out, c)
        st = dict(state)
        st["prev_tokens_in"] = x_in
        st["prev_hidden"] = hidden
        st["have_cache"] = jnp.ones_like(state["have_cache"])
        stats = dict(st["stats"])
        stats["blocks_computed"] = stats["blocks_computed"] + float(self.L)
        stats["blocks_run"] = stats["blocks_run"] + float(self.L)
        stats["motion_frac_sum"] = stats["motion_frac_sum"] + 1.0
        st["stats"] = stats
        return eps, st

    # ------------------------------------------------------------------
    # FastCache proper (Alg. 1), per-sample block gates
    # ------------------------------------------------------------------

    def _gated_step(self, params, state, x_in, c):
        fc = self.fc
        fcp = self.fc_params
        b, n, d = x_in.shape

        # ---- STR: token partition (Eqs. 1-2), per-sample
        with jax.named_scope("fastcache.partition"):
            if fc.use_str:
                sal = saliency.token_saliency(x_in, state["prev_tokens_in"])
                part = saliency.partition_tokens(sal, fc.motion_threshold,
                                                 self.capacity)
            else:
                sal = jnp.full((b, n), jnp.inf, F32)
                part = saliency.partition_tokens(sal, -1.0, n)
            mfrac = saliency.motion_fraction(part)           # (B,)

        # ---- static bypass (Eq. 3) + MB blend with previous final hidden
        with jax.named_scope("fastcache.bypass"):
            h_static = linear_approx.apply_linear(fcp["W_c"], fcp["b_c"],
                                                  x_in)
            if fc.use_mb:
                h_static = linear_approx.blend(h_static,
                                               state["prev_hidden"][-1],
                                               fc.blend_gamma)

        # ---- motion stream through gated blocks
        xm = saliency.gather_motion(x_in, part)              # (B,C,D)
        gate = state["gate"]
        # df of the chi^2 statistic = observed elements of ONE sample
        # (static at trace time; the paper's ND with the motion capacity
        # applied)
        nd = int(xm.shape[1] * xm.shape[2])
        threshold = statcache.make_threshold(fc.alpha, nd)
        if self.gate_mode == "global":
            threshold_g = statcache.make_threshold(fc.alpha, nd * b)
        use_sc = bool(fc.use_sc)

        # cache payload: block l reads last step's motion tokens of layers
        # l (its input) and l+1 (its output); each layer is gathered once,
        # layer l+1 by block l, which hands it on to block l+1
        with jax.named_scope("fastcache.payload"):
            keep = saliency.motion_keep(part)[..., None]     # (B,C,1)
            prev_m0 = saliency.gather_motion_layer(state["prev_hidden"], 0,
                                                   part)
            # the scan writes into the old stack's buffer, so every read of
            # it outside the scan is ordered first (else XLA copies it)
            h_static, prev_m0, hidden0 = jax.lax.optimization_barrier(
                (h_static, prev_m0, state["prev_hidden"]))

        def body(carry, xs):
            xm, prev_m, hidden, sig, ini, comp, skip, ran = carry
            bp, w_l, b_l, lidx = xs
            with jax.named_scope("fastcache.payload"):
                prev_om = saliency.gather_motion_layer(hidden, lidx + 1,
                                                       part)
            with jax.named_scope("fastcache.gate"):
                eligible = ini[lidx] & use_sc                # (B,)
                if self.gate_mode == "global":
                    diff, prevsq = statcache.delta_stats_per_sample(xm,
                                                                    prev_m)
                    do_cache = jnp.broadcast_to(
                        statcache.gate_decision_global(
                            diff, sig[lidx], nd * b, threshold_g)
                        & jnp.all(eligible), (b,))
                    approx = linear_approx.apply_linear(w_l, b_l, xm)
                    if fc.use_mb:
                        approx = linear_approx.blend(approx, prev_om,
                                                     fc.blend_gamma)
                    out = jnp.where(do_cache[:, None, None], approx, xm)
                else:
                    gate_fn = (kernel_ops.fused_gate if self.use_fused
                               else kernel_ref.fused_gate)
                    out, do_cache, diff, prevsq = gate_fn(
                        xm, prev_m, prev_om, w_l, b_l, sig[lidx], eligible,
                        threshold=threshold, gamma=fc.blend_gamma,
                        use_blend=fc.use_mb)

            # skip the MXU block entirely when every sample caches;
            # otherwise compute it once for the batch and keep cached
            # samples' approx
            with jax.named_scope("fastcache.block"):
                xm_new = jax.lax.cond(
                    jnp.all(do_cache),
                    lambda ops_: ops_[0],
                    lambda ops_: jnp.where(
                        do_cache[:, None, None], ops_[0],
                        self.model.block_apply(bp, ops_[1], c)),
                    (out, xm))
            # keep the motion-stream carry on its slot shards (serving
            # runs this scan under a (data, model) mesh; without the
            # constraint GSPMD is free to gather the carry onto one device
            # per layer)
            xm_new = constrain(xm_new, "act_batch", "act_seq", "act_embed")
            # sliding-window variance tracker updates on recompute,
            # per-sample
            with jax.named_scope("fastcache.gate"):
                new_sig, _ = statcache.update_sigma(
                    sig[lidx], ini[lidx], diff, nd, fc.background_momentum)
                sig = sig.at[lidx].set(jnp.where(do_cache, sig[lidx],
                                                 new_sig))
                ini = ini.at[lidx].set(jnp.ones_like(ini[lidx]))
            dc = do_cache.astype(F32)
            comp = comp + (1.0 - dc)
            skip = skip + dc
            # the block runs for every row unless every row caches
            ran = ran + jnp.where(jnp.all(do_cache), 0.0, 1.0)
            # cache payload, in place: this block's input over its motion
            # tokens (those the tau_s gate kept); every other token of
            # layer l keeps last step's value, which it already holds
            with jax.named_scope("fastcache.payload"):
                hidden = saliency.set_motion_layer(
                    hidden, lidx, jnp.where(keep, xm, prev_m), part)
            return (xm_new, prev_om, hidden, sig, ini, comp, skip, ran), None

        lidx = jnp.arange(self.L)
        carry0 = (xm, prev_m0, hidden0, gate.sigma2, gate.initialized,
                  jnp.zeros((b,), F32), jnp.zeros((b,), F32),
                  jnp.zeros((), F32))
        (xm, _, hidden, sig, ini, comp, skip, ran), _ = jax.lax.scan(
            body, carry0, (params["blocks"], fcp["W_l"], fcp["b_l"], lidx))

        # ---- reassemble full grid (concat of Eq. 2 sets)
        h_final = saliency.scatter_motion(h_static, xm, part)
        eps = self._eps(params, h_final, c)

        st = dict(state)
        st["prev_tokens_in"] = x_in
        with jax.named_scope("fastcache.payload"):
            st["prev_hidden"] = hidden.at[self.L].set(
                h_final.astype(hidden.dtype))
        st["gate"] = statcache.GateState(sigma2=sig, initialized=ini)
        stats = dict(st["stats"])
        stats["blocks_computed"] = stats["blocks_computed"] + comp
        stats["blocks_skipped"] = stats["blocks_skipped"] + skip
        stats["blocks_run"] = stats["blocks_run"] + ran
        stats["motion_frac_sum"] = stats["motion_frac_sum"] + mfrac
        st["stats"] = stats
        return eps, st

    def _mixed_step(self, params, state, x_in, c, have):
        """Mixed warm/cold batch (a request admitted mid-flight): cold
        samples take a full forward (their warm-up step), warm samples take
        the gated fastcache path.  Results and state are selected
        per-sample, so a warm sample's outputs, cache payload, variance
        trackers and stats are bit-identical to a run where the admission
        never happened, and a cold sample's match its own solo warm-up
        step."""
        warm = have                                          # (B,)
        with jax.named_scope("fastcache.full_forward"):
            x_out, inputs = self._full_forward(params, x_in, c)
        hidden = jnp.concatenate([inputs, x_out[None]], axis=0)
        eps_full = self._eps(params, x_out, c)
        eps_fc, st_fc = self._gated_step(params, state, x_in, c)

        w3 = warm[:, None, None]
        w4 = warm[:, None, None, None]
        eps = jnp.where(w4, eps_fc, eps_full.astype(eps_fc.dtype))
        st = dict(st_fc)
        st["prev_tokens_in"] = jnp.where(w3, st_fc["prev_tokens_in"], x_in)
        st["prev_hidden"] = jnp.where(
            warm[None, :, None, None], st_fc["prev_hidden"],
            hidden.astype(st_fc["prev_hidden"].dtype))
        # cold samples' warm-up leaves the gate untouched (matching
        # _cold_step): trackers first observe a delta on the NEXT step,
        # against the real payload installed here
        st["gate"] = statcache.GateState(
            sigma2=jnp.where(warm[None, :], st_fc["gate"].sigma2,
                             state["gate"].sigma2),
            initialized=jnp.where(warm[None, :], st_fc["gate"].initialized,
                                  state["gate"].initialized))
        st["have_cache"] = jnp.ones_like(have)
        old = state["stats"]
        stats = dict(st_fc["stats"])
        stats["blocks_computed"] = jnp.where(
            warm, stats["blocks_computed"], old["blocks_computed"] + self.L)
        for k in ("blocks_skipped", "steps_reused"):
            stats[k] = jnp.where(warm, stats[k], old[k])
        # every row ran the full forward and the gated path's blocks
        stats["blocks_run"] = stats["blocks_run"] + self.L
        stats["motion_frac_sum"] = jnp.where(
            warm, stats["motion_frac_sum"], old["motion_frac_sum"] + 1.0)
        st["stats"] = stats
        return eps, st
