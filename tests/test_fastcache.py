"""FastCache core semantics: saliency partition, chi^2 gate, linear
calibration, token merging, cache policies, and the paper's claimed
behaviours (error bound Eq. 9, alpha-monotone cache rate — Fig. 3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.configs.base import FastCacheConfig
from repro.core import (CachedDecoder, CachedDiT, chi2_ppf, error_bound,
                        summarize_stats)
from repro.core import linear_approx, saliency, statcache, token_merge
from repro.core.policies.base import F32
from repro.core.policies.fastcache import FastCache
from repro.kernels import ref as kernel_ref
from repro.models import build_model
from repro.models.dit import unzero_params
from tests.conftest import f32_cfg


# ---------------------------------------------------------------------------
# chi^2 / statistical gate
# ---------------------------------------------------------------------------

def test_chi2_ppf_matches_scipy():
    scipy = pytest.importorskip("scipy.stats")
    for df in (30, 1000, 300_000):
        for p in (0.9, 0.95, 0.99):
            assert abs(chi2_ppf(p, df) - scipy.chi2.ppf(p, df)) \
                / scipy.chi2.ppf(p, df) < 1e-3


def test_error_bound_eq9_shrinks_with_alpha():
    # higher confidence (smaller alpha) => larger threshold => larger bound
    nd = 64 * 256
    bounds = [error_bound(a, nd) for a in (0.2, 0.1, 0.05, 0.01)]
    assert all(b2 > b1 for b1, b2 in zip(bounds, bounds[1:]))
    # and the bound is ~1 for big ND (relative-change scale)
    assert 0.9 < bounds[0] < 1.2


def test_gate_decision_normalized_alpha_monotone(key):
    """Larger alpha => smaller threshold => fewer skips (Fig. 3 direction)."""
    nd = 4096
    h_prev = jax.random.normal(key, (64, 64))
    noise = 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (64, 64))
    h = h_prev + noise
    diff, prev = statcache.delta_stats(h, h_prev)
    sigma2 = jnp.asarray(0.01)  # matched to the noise scale
    skips = []
    for alpha in (0.5, 0.1, 0.01):
        thr = statcache.make_threshold(alpha, nd)
        skips.append(bool(statcache.gate_decision(diff, prev, sigma2, nd,
                                                  thr)))
    # thresholds increase as alpha decreases
    t1 = statcache.make_threshold(0.5, nd)
    t2 = statcache.make_threshold(0.01, nd)
    assert t2 > t1


def test_gate_identical_hidden_always_caches(key):
    h = jax.random.normal(key, (32, 32))
    diff, prev = statcache.delta_stats(h, h)
    thr = statcache.make_threshold(0.05, h.size)
    assert bool(statcache.gate_decision(diff, prev, jnp.asarray(1.0), h.size,
                                        thr))


def test_gate_huge_change_never_caches(key):
    h = jax.random.normal(key, (32, 32))
    diff, prev = statcache.delta_stats(h * 100.0, h)
    thr = statcache.make_threshold(0.05, h.size)
    assert not bool(statcache.gate_decision(diff, prev, jnp.asarray(1.0),
                                            h.size, thr))


# ---------------------------------------------------------------------------
# Saliency / partition
# ---------------------------------------------------------------------------

def test_partition_invariants(key):
    x = jax.random.normal(key, (2, 32, 16))
    xp = x.at[:, :8].add(3.0)  # first 8 tokens moved
    sal = saliency.token_saliency(x, xp)
    part = saliency.partition_tokens(sal, tau_s=0.5, capacity=8)
    # exactly the moved tokens are motion
    assert bool(jnp.all(part.is_motion[:, :8]))
    assert not bool(jnp.any(part.is_motion[:, 8:]))
    # gather/scatter roundtrip: scatter(gather(x)) == x at motion positions
    xm = saliency.gather_motion(x, part)
    back = saliency.scatter_motion(jnp.zeros_like(x), xm, part)
    np.testing.assert_allclose(back[:, :8], x[:, :8], atol=1e-6)
    np.testing.assert_allclose(back[:, 8:], 0.0)


def test_partition_capacity_overflow_is_conservative(key):
    x = jax.random.normal(key, (1, 16, 8))
    xp = x + 1.0  # every token moved
    sal = saliency.token_saliency(x, xp)
    part = saliency.partition_tokens(sal, tau_s=0.0, capacity=4)
    assert int(part.is_motion.sum()) == 4  # capacity-bounded


# ---------------------------------------------------------------------------
# Linear approximation + calibration
# ---------------------------------------------------------------------------

def test_fit_linear_recovers_exact_map(key):
    d = 16
    w_true = jax.random.normal(key, (d, d)) * 0.3
    b_true = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    x = jax.random.normal(jax.random.fold_in(key, 2), (512, d))
    y = x @ w_true + b_true
    w, b = linear_approx.fit_linear(x, y, ridge=1e-8)
    np.testing.assert_allclose(w, w_true, atol=1e-3)
    np.testing.assert_allclose(b, b_true, atol=1e-3)


def test_identity_init_is_passthrough(key):
    p = linear_approx.init_linear_params(3, 8)
    x = jax.random.normal(key, (4, 8))
    np.testing.assert_allclose(
        linear_approx.apply_linear(p["W_l"][1], p["b_l"][1], x), x,
        atol=1e-6)


def test_calibration_reduces_block_approx_error(key):
    cfg = f32_cfg(get_reduced("dit-b2"))
    model = build_model(cfg)
    params = model.init(key)
    # adaLN-zero init makes blocks the identity — un-zero the gates so the
    # blocks actually transform (as a trained model would)
    params["blocks"]["ada_w"] = 0.05 * jax.random.normal(
        jax.random.fold_in(key, 7), params["blocks"]["ada_w"].shape)
    params["blocks"]["ada_b"] = 0.2 * jax.random.normal(
        jax.random.fold_in(key, 8), params["blocks"]["ada_b"].shape)
    img, ch = cfg.dit.image_size, cfg.dit.in_channels
    batches = [{"latents": jax.random.normal(jax.random.fold_in(key, i),
                                             (2, img, img, ch)),
                "t": jnp.array([10 * i + 1, 20 * i + 2]),
                "labels": jnp.array([i % 10, (i + 1) % 10])}
               for i in range(3)]
    ident = linear_approx.init_linear_params(cfg.num_layers, cfg.d_model)
    fit = linear_approx.calibrate_dit(model, params, ident, batches)

    # in-sample: least squares must beat the identity bypass (identity+0 is
    # inside the hypothesis class) — this is the paper's quality edge over
    # reuse-style caches (§ Zero-Shot Redundancy Reduction)
    err_ident, err_fit, n = 0.0, 0.0, 0
    for b in batches:
        x = model.tokens_in(params, b["latents"])
        c = model.conditioning(params, b["t"], b["labels"])
        bp = jax.tree.map(lambda a: a[0], params["blocks"])
        y = model.block_apply(bp, x, c)
        err_ident += float(jnp.sum((x - y) ** 2))
        approx = linear_approx.apply_linear(fit["W_l"][0], fit["b_l"][0], x)
        err_fit += float(jnp.sum((approx - y) ** 2))
        n += y.size
    assert err_fit < err_ident


# ---------------------------------------------------------------------------
# Token merging (CTM)
# ---------------------------------------------------------------------------

def test_merge_unmerge_shapes_and_identity_clusters(key):
    b, n, d, w = 2, 64, 16, 16
    h = jax.random.normal(key, (b, n, d))
    merged, mm = token_merge.merge_tokens(h, h, window=w, keep_ratio=0.5,
                                          k=5, lam=1.0)
    assert merged.shape == (b, n // 2, d)
    restored = token_merge.unmerge_tokens(merged, mm, window=w, n_tokens=n)
    assert restored.shape == h.shape
    # keep_ratio=1: every token is its own center -> lossless roundtrip
    merged2, mm2 = token_merge.merge_tokens(h, h, window=w, keep_ratio=1.0,
                                            k=5, lam=1.0)
    restored2 = token_merge.unmerge_tokens(merged2, mm2, window=w,
                                           n_tokens=n)
    np.testing.assert_allclose(restored2, h, atol=1e-4)
    # every restored token equals one of its window's merged representatives
    # (the stored mapping M of Alg. 2 is valid)
    mw = merged.reshape(2, n // w, -1, d)
    for bi in range(2):
        for wi in range(n // w):
            rw = restored.reshape(2, n // w, w, d)[bi, wi]
            d2 = jnp.sum((rw[:, None] - mw[bi, wi][None]) ** 2, -1)
            assert float(d2.min(axis=1).max()) < 1e-8


def test_merged_token_is_weighted_mean_in_hull(key):
    b, n, d, w = 1, 16, 8, 16
    h = jax.random.normal(key, (b, n, d))
    merged, _ = token_merge.merge_tokens(h, h, window=w, keep_ratio=0.25,
                                         k=3, lam=0.5)
    lo = h.min(axis=1, keepdims=True)
    hi = h.max(axis=1, keepdims=True)
    assert bool(jnp.all(merged >= lo - 1e-4))
    assert bool(jnp.all(merged <= hi + 1e-4))


def test_knn_density_higher_in_clusters(key):
    # one tight cluster + outliers: cluster tokens must have higher rho
    cluster = 0.01 * jax.random.normal(key, (1, 8, 4))
    outliers = 5.0 + jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 4)) * 3
    h = jnp.concatenate([cluster, outliers], axis=1)
    rho = token_merge.knn_density(h, k=3)
    assert float(rho[0, :8].min()) > float(rho[0, 8:].max())


# ---------------------------------------------------------------------------
# Policies / runners
# ---------------------------------------------------------------------------

def _setup_dit(key, policy, fc=None, **kw):
    cfg = f32_cfg(get_reduced("dit-b2"))
    model = build_model(cfg)
    params = model.init(key)
    runner = CachedDiT(model, fc or FastCacheConfig(), policy=policy, **kw)
    return cfg, model, params, runner


def _drive(runner, params, key, cfg, steps=6, shrink=0.02):
    b = 2
    img, ch = cfg.dit.image_size, cfg.dit.in_channels
    x = jax.random.normal(key, (b, img, img, ch))
    state = runner.init_state(b)
    step = jax.jit(runner.step)
    labels = jnp.array([1, 2])
    outs = []
    for t in range(steps):
        eps, state = step(params, state, x, jnp.full((b,), 50 - t), labels)
        outs.append(eps)
        x = x - shrink * eps
    return outs, state


def test_nocache_counts_all_blocks(key):
    cfg, model, params, runner = _setup_dit(key, "nocache")
    outs, state = _drive(runner, params, key, cfg)
    s = summarize_stats(state)
    assert s["block_cache_ratio"] == 0.0
    assert s["steps_reused"] == 0.0


def test_fora_reuses_fixed_interval(key):
    cfg, model, params, runner = _setup_dit(key, "fora", fora_interval=3)
    outs, state = _drive(runner, params, key, cfg, steps=6)
    s = summarize_stats(state)
    assert s["steps_reused"] == 4.0  # steps 1,2,4,5


def test_fastcache_skips_when_static(key):
    cfg, model, params, runner = _setup_dit(key, "fastcache")
    # identical inputs after step 2 -> gate must cache heavily
    b = 2
    img, ch = cfg.dit.image_size, cfg.dit.in_channels
    x = jax.random.normal(key, (b, img, img, ch))
    state = runner.init_state(b)
    step = jax.jit(runner.step)
    labels = jnp.array([1, 2])
    for t in range(6):
        eps, state = step(params, state, x, jnp.full((b,), 25), labels)
    s = summarize_stats(state)
    assert s["block_cache_ratio"] > 0.4, s
    # and the static-token fraction must be high (inputs identical)
    assert s["mean_motion_fraction"] < 0.5, s


def test_fastcache_output_close_to_nocache(key):
    cfg, model, params, r_nc = _setup_dit(key, "nocache")
    _, _, _, r_fc = _setup_dit(key, "fastcache")
    outs_nc, _ = _drive(r_nc, params, key, cfg)
    outs_fc, state = _drive(r_fc, params, key, cfg)
    rel = [float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(a) + 1e-9))
           for a, b in zip(outs_nc, outs_fc)]
    # Eq. 9-style bounded deviation (loose engineering bound)
    assert max(rel) < 1.5, rel


class _CopyingFastCache(FastCache):
    """FastCache with the payload update it had before the in-place one:
    the stack sliced into block inputs and outputs, each block's inputs
    scattered over a full copy of its layer, and the copies concatenated
    with the final hidden.  The reference for the in-place update."""

    def _gated_step(self, params, state, x_in, c):
        fc, fcp = self.fc, self.fc_params
        b = x_in.shape[0]
        sal = saliency.token_saliency(x_in, state["prev_tokens_in"])
        part = saliency.partition_tokens(sal, fc.motion_threshold,
                                         self.capacity)
        h_static = linear_approx.blend(
            linear_approx.apply_linear(fcp["W_c"], fcp["b_c"], x_in),
            state["prev_hidden"][-1], fc.blend_gamma)
        xm = saliency.gather_motion(x_in, part)
        nd = int(xm.shape[1] * xm.shape[2])
        threshold = statcache.make_threshold(fc.alpha, nd)
        threshold_g = statcache.make_threshold(fc.alpha, nd * b)

        def body(carry, xs):
            xm, sig, ini, comp, skip, ran = carry
            bp, w_l, b_l, prev_in, prev_out, lidx = xs
            prev_m = saliency.gather_motion(prev_in, part)
            prev_om = saliency.gather_motion(prev_out, part)
            eligible = ini[lidx]
            if self.gate_mode == "global":
                diff, _ = statcache.delta_stats_per_sample(xm, prev_m)
                do_cache = jnp.broadcast_to(
                    statcache.gate_decision_global(
                        diff, sig[lidx], nd * b, threshold_g)
                    & jnp.all(eligible), (b,))
                approx = linear_approx.blend(
                    linear_approx.apply_linear(w_l, b_l, xm), prev_om,
                    fc.blend_gamma)
                out = jnp.where(do_cache[:, None, None], approx, xm)
            else:
                out, do_cache, diff, _ = kernel_ref.fused_gate(
                    xm, prev_m, prev_om, w_l, b_l, sig[lidx], eligible,
                    threshold=threshold, gamma=fc.blend_gamma)
            xm_new = jax.lax.cond(
                jnp.all(do_cache), lambda o: o[0],
                lambda o: jnp.where(do_cache[:, None, None], o[0],
                                    self.model.block_apply(bp, o[1], c)),
                (out, xm))
            new_sig, _ = statcache.update_sigma(
                sig[lidx], ini[lidx], diff, nd, fc.background_momentum)
            sig = sig.at[lidx].set(jnp.where(do_cache, sig[lidx], new_sig))
            ini = ini.at[lidx].set(jnp.ones_like(ini[lidx]))
            dc = do_cache.astype(F32)
            ran = ran + jnp.where(jnp.all(do_cache), 0.0, 1.0)
            return ((xm_new, sig, ini, comp + 1.0 - dc, skip + dc, ran),
                    saliency.scatter_motion(prev_in, xm, part))

        gate = state["gate"]
        (xm, sig, ini, comp, skip, ran), new_prev_in = jax.lax.scan(
            body, (xm, gate.sigma2, gate.initialized, jnp.zeros((b,), F32),
                   jnp.zeros((b,), F32), jnp.zeros((), F32)),
            (params["blocks"], fcp["W_l"], fcp["b_l"],
             state["prev_hidden"][:-1], state["prev_hidden"][1:],
             jnp.arange(self.L)))
        h_final = saliency.scatter_motion(h_static, xm, part)
        st = dict(state)
        st["prev_tokens_in"] = x_in
        st["prev_hidden"] = jnp.concatenate([new_prev_in, h_final[None]], 0)
        st["gate"] = statcache.GateState(sigma2=sig, initialized=ini)
        stats = dict(st["stats"])
        stats["blocks_computed"] = stats["blocks_computed"] + comp
        stats["blocks_skipped"] = stats["blocks_skipped"] + skip
        stats["blocks_run"] = stats["blocks_run"] + ran
        stats["motion_frac_sum"] = (stats["motion_frac_sum"]
                                    + saliency.motion_fraction(part))
        st["stats"] = stats
        return self._eps(params, h_final, c), st


@pytest.mark.parametrize("gate_mode", ["per_sample", "global"])
def test_fastcache_in_place_payload_matches_copying_update(gate_mode):
    """The in-place payload update leaves eps and the whole state (payload
    stack, gate trackers, counters) bitwise where the copying update does,
    step after step: gated steps on which the tau gate leaves some of the
    top-C tokens static, and a mixed step after a mid-flight admission."""
    cfg = get_reduced("dit-b2").replace(num_layers=4)
    model = build_model(cfg)
    params = unzero_params(model.init(jax.random.PRNGKey(0)),
                           jax.random.PRNGKey(1))
    fc = FastCacheConfig(gate_mode=gate_mode, use_fused_gate=False)
    runner = CachedDiT(model, fc, policy="fastcache")
    ref = CachedDiT(model, fc, policy="fastcache")
    ref.impl = _CopyingFastCache(model, fc, ref.fc_params,
                                 gate_mode=gate_mode, use_fused=False)
    b, img, ch = 4, cfg.dit.image_size, cfg.dit.in_channels
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (b, img, img, ch))
    labels = jnp.arange(b)
    step, step_ref = jax.jit(runner.step), jax.jit(ref.step)
    state = runner.init_state(b)
    state_ref = ref.init_state(b)
    for i in range(7):
        if i == 4:       # a request admitted into row 1: a mixed step
            state = runner.reset_slot(state, jnp.array([1]))
            state_ref = ref.reset_slot(state_ref, jnp.array([1]))
        t = jnp.full((b,), 40 - 3 * i)
        eps, state = step(params, state, x, t, labels)
        eps_ref, state_ref = step_ref(params, state_ref, x, t, labels)
        np.testing.assert_array_equal(np.asarray(eps), np.asarray(eps_ref))
        jax.tree.map(lambda a, r: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(r)), state, state_ref)
        # only the top rows of the latents move, by a step-dependent
        # amount: the tokens of the other rows are static
        noise = jax.random.normal(jax.random.fold_in(key, i), x.shape)
        x = x.at[:, :img // 4].add((0.5 if i % 2 else 0.02)
                                   * noise[:, :img // 4])
    s = summarize_stats(state)
    # the tau gate left top-C tokens static (motion below the capacity)
    assert s["mean_motion_fraction"] < fc.motion_capacity, s
    # and the gate both cached and computed blocks
    assert 0.0 < s["block_cache_ratio"] < 1.0, s


def test_l2c_respects_mask(key):
    cfg = f32_cfg(get_reduced("dit-b2"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mask = jnp.zeros((cfg.num_layers,), bool).at[0].set(True)
    runner = CachedDiT(model, FastCacheConfig(), policy="l2c",
                       l2c_mask=mask)
    outs, state = _drive(runner, params, jax.random.PRNGKey(1), cfg,
                         steps=4)
    s = summarize_stats(state)
    assert s["blocks_skipped"] == 4.0  # 1 layer x 4 steps


def test_decode_runner_matches_exact_when_gate_off(key):
    cfg = f32_cfg(get_reduced("qwen3-0.6b"))
    model = build_model(cfg)
    params = model.init(key)
    toks = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    fc = FastCacheConfig(use_sc=False)     # gate disabled -> exact decode
    dec = CachedDecoder(model, fc)
    st = dec.init_state(2)
    logits_ref, cache_ref = model.prefill(params, {"tokens": toks},
                                          window=32)
    logits_fc, cache_fc = model.prefill(params, {"tokens": toks}, window=32)
    for t in range(4):
        nxt = jnp.argmax(logits_ref, -1).astype(jnp.int32)
        logits_ref, cache_ref = model.decode_step(params, nxt, cache_ref)
        logits_fc, cache_fc, st = dec.decode_step(params, nxt, cache_fc, st)
        np.testing.assert_allclose(logits_fc, logits_ref, atol=1e-4)
    assert float(jnp.sum(st["stats"]["blocks_skipped"])) == 0.0
