"""The plain reference: each request sampled alone, in float32 at the
highest matmul precision, from the papers.

  DiT            Peebles & Xie 2023 (arXiv:2212.09748): patch tokens,
                 adaLN-zero blocks, sine-cosine timestep embedding.
  guidance       classifier-free guidance, eps_u + g (eps_c - eps_u); a
                 scale of 1 takes the conditional eps as it is.
  sampler        deterministic DDIM over DDPM's linear beta schedule.
  FastCache      arXiv:2505.20353 (Alg. 1): spatial token reduction (the
                 top-C tokens by temporal saliency whose saliency exceeds
                 tau are motion tokens, the rest take the linear bypass
                 blended with the previous step's final hidden), a per-block
                 chi-square gate on the motion tokens' change against a
                 sliding variance tracker, and for a cached block the linear
                 approximation blended with the block's previous output.
                 The first step of a request runs every block on every
                 token.  The linear maps are the paper's initialisation,
                 identity and zero bias (no calibration).
  token merging  the paper's Eqs. 10-13 in windows of w tokens: kNN density
                 times (1 + lambda * motion) scores each token, the top M
                 of a window become centres, every token joins its nearest
                 centre, a centre carries the score-weighted mean of its
                 members, and the final hidden is unmerged by copying each
                 centre back to its members.

It imports nothing of the serving program.  It reads the weights the
benchmark made (their layout is in ``bench/weights.py``) and makes each
request's noise from the request's noise seed.  There is no kernel, no
serving engine and no coupling between requests: every decision is taken
per row, as the serving program promises for its batch.

Departures, all of layout: a patch's vector is ordered (row, column,
channel), tokens run row-major over the grid, and under ``learn_sigma`` the
final projection holds the eps outputs first.  The windowed kNN and the
fixed centre count M = ceil(ratio * w) are the serving program's stated
adaptation of the paper's global clustering, and are followed here.

``quant=True`` rounds every matmul operand to float8 e4m3 under a scale per
tensor: the control that the benchmark's comparison has to reject.

A teacher-forced step (``guided_step`` with ``force``) starts from a
program's latents and cache state and takes the program's motion tokens and
cached blocks as given; ``rule_breaks`` holds those decisions, and the
trackers, to Alg. 1 applied to the program's own values.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Dims, request_noise

F32 = jnp.float32
NUM_TRAIN_STEPS = 1000
FP8_MAX = 448.0          # largest finite float8_e4m3fn
HIGHEST = jax.lax.Precision.HIGHEST


class Algo(NamedTuple):
    """What a configuration serves, in the reference's terms."""
    fastcache: bool = False
    capacity: float = 0.5        # motion tokens, share of the grid
    tau: float = 0.05            # saliency a motion token must exceed
    alpha: float = 0.05          # gate significance
    gamma: float = 0.5           # blend weight of the linear approximation
    momentum: float = 0.7        # variance tracker
    merge_window: int = 0        # 0: no token merging
    merge_ratio: float = 0.5
    knn_k: int = 5
    merge_lambda: float = 1.0


def algo_of(cfg: Dict) -> Algo:
    """The configuration file's policy and cache settings (the serving
    program's defaults where the file leaves a setting out)."""
    fc = dict(cfg.get("fastcache", {}))
    if cfg["policy"] not in ("fastcache", "nocache"):
        raise ValueError(f"the reference serves fastcache and nocache, not "
                         f"{cfg['policy']!r}")
    merge = fc.get("merge_enabled", False)
    return Algo(fastcache=cfg["policy"] == "fastcache",
                capacity=fc.get("motion_capacity", 0.5),
                tau=fc.get("motion_threshold", 0.05),
                alpha=fc.get("alpha", 0.05),
                gamma=fc.get("blend_gamma", 0.5),
                momentum=fc.get("background_momentum", 0.7),
                merge_window=fc.get("merge_window", 16) if merge else 0,
                merge_ratio=fc.get("merge_ratio", 0.5),
                knn_k=fc.get("knn_k", 5),
                merge_lambda=fc.get("merge_lambda", 1.0))


# --------------------------------------------------------------------------
# the sampler's schedule
# --------------------------------------------------------------------------

def alphas_cumprod(num_train_steps: int = NUM_TRAIN_STEPS) -> np.ndarray:
    """DDPM's linear beta schedule (1e-4 to 0.02), cumulative products."""
    betas = np.linspace(1e-4, 0.02, num_train_steps, dtype=np.float64)
    return np.cumprod(1.0 - betas)


def ddim_timesteps(num_steps: int, num_train_steps: int = NUM_TRAIN_STEPS
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Evenly strided timesteps from T-1 down, and each step's target
    timestep (-1 on the final, x0-predicting step)."""
    stride = num_train_steps // num_steps
    ts = np.arange(num_train_steps - 1, -1, -stride)
    prev = np.append(ts[1:], -1)
    return ts[:num_steps], prev[:num_steps]


# --------------------------------------------------------------------------
# arithmetic
# --------------------------------------------------------------------------

def _fake_quant(x: jax.Array, batch_axes: int) -> jax.Array:
    """Round to float8 e4m3 under one scale per leading ``batch_axes``
    index (a whole weight matrix, or one row's activations)."""
    axes = tuple(range(batch_axes, x.ndim))
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(eq: str, a, b, quant: bool, a_batch: int = 1, b_batch: int = 0):
    if quant:
        a, b = _fake_quant(a, a_batch), _fake_quant(b, b_batch)
    return jnp.einsum(eq, a, b, precision=HIGHEST,
                      preferred_element_type=F32)


def _layer_norm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6)


def _modulate(x, shift, scale):
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _take(x, idx):
    """x (B, N, D), idx (B, C) -> (B, C, D)."""
    return jnp.take_along_axis(x, idx[..., None], axis=1)


def _put(x, idx, rows):
    """x with x[b, idx[b]] = rows[b]."""
    return x.at[jnp.arange(x.shape[0])[:, None], idx].set(rows)


# --------------------------------------------------------------------------
# DiT
# --------------------------------------------------------------------------

def tokens_in(p, latents, d: Dims, quant):
    b, g, ps, c = latents.shape[0], d.grid, d.patch, d.channels
    tok = latents.reshape(b, g, ps, g, ps, c).transpose(0, 1, 3, 2, 4, 5)
    tok = tok.reshape(b, g * g, ps * ps * c)
    return (_mm("bnp,pd->bnd", tok, p["patch_w"], quant) + p["patch_b"]
            + p["pos_emb"][None])


def conditioning(p, t, labels, quant):
    """silu of the timestep + class embedding, (B, D)."""
    half = 128
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=F32) / half)
    args = t.astype(F32)[:, None] * freqs[None]
    temb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    temb = jax.nn.silu(_mm("bf,fd->bd", temb, p["t_w1"], quant) + p["t_b1"])
    temb = _mm("bf,fd->bd", temb, p["t_w2"], quant) + p["t_b2"]
    return jax.nn.silu(temb + p["label_emb"][labels])


def block(bp, x, cs, d: Dims, quant):
    mod = _mm("bd,de->be", cs, bp["ada_w"], quant) + bp["ada_b"]
    sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
    h = _modulate(_layer_norm(x), sh1, sc1)
    q = _mm("bnd,dhk->bnhk", h, bp["wq"], quant)
    k = _mm("bnd,dhk->bnhk", h, bp["wk"], quant)
    v = _mm("bnd,dhk->bnhk", h, bp["wv"], quant)
    s = _mm("bqhk,bshk->bhqs", q, k, quant, 1, 1) * d.head_dim ** -0.5
    o = _mm("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v, quant, 1, 1)
    x = x + g1[:, None, :] * _mm("bnhk,hkd->bnd", o, bp["wo"], quant)
    h = _modulate(_layer_norm(x), sh2, sc2)
    h = _gelu_tanh(_mm("bnd,df->bnf", h, bp["w_in"], quant) + bp["b_in"])
    h = _mm("bnf,fd->bnd", h, bp["w_out"], quant) + bp["b_out"]
    return x + g2[:, None, :] * h


def final_layer(p, x, cs, d: Dims, quant):
    """Final hidden (B, N, D) -> eps latents (B, side, side, C)."""
    b, g, ps, c = x.shape[0], d.grid, d.patch, d.channels
    mod = _mm("bd,de->be", cs, p["final_ada_w"], quant) + p["final_ada_b"]
    shift, scale = jnp.split(mod, 2, axis=-1)
    out = _mm("bnd,do->bno", _modulate(_layer_norm(x), shift, scale),
              p["final_w"], quant) + p["final_b"]
    eps = out[..., :d.patch_dim].reshape(b, g, g, ps, ps, c)
    return eps.transpose(0, 1, 3, 2, 4, 5).reshape(b, g * ps, g * ps, c)


def full_forward(p, x, cs, d: Dims, quant):
    """Every block on every token: (final hidden, the blocks' inputs)."""
    def body(x, bp):
        return block(bp, x, cs, d, quant), x
    return jax.lax.scan(body, x, p["blocks"])


# --------------------------------------------------------------------------
# token merging
# --------------------------------------------------------------------------

def merge(x, prev, a: Algo, quant, force=None):
    """(B, N, D) tokens -> (B, N*M/w, D) centres, each token's centre
    (B, N/w, w) and the centres' tokens (B, N/w, M).  ``force`` = (centres'
    tokens, each token's centre) takes those decisions as given."""
    b, n, dd = x.shape
    w = a.merge_window
    m = min(w, max(1, math.ceil(a.merge_ratio * w)))
    hw = x.reshape(b, n // w, w, dd)
    sq = jnp.sum(hw * hw, axis=-1)
    dist = (sq[..., :, None] + sq[..., None, :]
            - 2.0 * _mm("bwid,bwjd->bwij", hw, hw, quant, 2, 2))
    dist = jnp.where(jnp.eye(w, dtype=bool), jnp.inf, jnp.maximum(dist, 0.0))
    near = -jax.lax.top_k(-dist, a.knn_k)[0]
    density = jnp.exp(-jnp.mean(near, axis=-1) / dd)
    motion = jnp.linalg.norm(hw - prev.reshape(hw.shape), axis=-1)
    score = density * (1.0 + a.merge_lambda * motion)
    score = score / jnp.maximum(jnp.max(score, -1, keepdims=True), 1e-30)
    if force is None:
        _, centres = jax.lax.top_k(score, m)                  # (B, W, M)
        ch = jnp.take_along_axis(hw, centres[..., None], axis=2)
        d2 = (sq[..., :, None] + jnp.sum(ch * ch, -1)[..., None, :]
              - 2.0 * _mm("bwid,bwjd->bwij", hw, ch, quant, 2, 2))
        assign = jnp.argmin(d2, axis=-1)                       # (B, W, w)
    else:
        centres, assign = force
    wgt = jax.nn.one_hot(assign, m, dtype=F32) * score[..., None]
    num = jnp.einsum("bwim,bwid->bwmd", wgt, hw, precision=HIGHEST)
    den = jnp.maximum(jnp.sum(wgt, axis=2), 1e-9)
    return (num / den[..., None]).reshape(b, -1, dd), assign, centres


@functools.partial(jax.jit, static_argnames=("a",))
def merge_decisions(x, prev, a: Algo):
    """The centres and assignments that Eqs. 10-13 give for the full
    tokens ``x`` after ``prev``, in float32."""
    _, assign, centres = merge(x, prev, a, False)
    return centres, assign


def unmerge(h, assign):
    b, nw, w = assign.shape
    hw = h.reshape(b, nw, -1, h.shape[-1])
    return jnp.take_along_axis(hw, assign[..., None], axis=2).reshape(
        b, nw * w, h.shape[-1])


# --------------------------------------------------------------------------
# FastCache
# --------------------------------------------------------------------------

def gate_threshold(alpha: float, nd: int) -> float:
    """chi^2_{nd, 1-alpha} / nd (Eq. 7 on the normalised statistic)."""
    from scipy.stats import chi2
    return float(chi2.ppf(1.0 - alpha, nd)) / nd


def fastcache_warm(p, st, x, cs, d: Dims, a: Algo, quant, force=None):
    """One cached step of Alg. 1 on the (B, N, D) tokens ``x``.  Returns
    (final hidden, new state).  ``force`` = (motion index (B, C), motion
    flag (B, C), cached (L, B)) takes those decisions as given instead of
    taking them from the statistics (a teacher-forced step)."""
    b, n, dd = x.shape
    cap = max(1, int(round(a.capacity * n)))
    lin = (lambda v: _fake_quant(v, 1)) if quant else (lambda v: v)
    if force is None:
        sal = jnp.sum(jnp.square(x - st["tokens"]), axis=-1)   # (B, N)
        _, idx = jax.lax.top_k(sal, cap)
        keep = jnp.take_along_axis(sal, idx, axis=1) > a.tau
        forced = jnp.zeros_like(st["init"])
    else:
        idx, keep, forced = force
    keep = keep[..., None]
    static = a.gamma * lin(x) + (1.0 - a.gamma) * st["hidden"][-1]
    nd = cap * dd
    thr = gate_threshold(a.alpha, nd)

    def body(carry, xs):
        xm, = carry
        bp, prev_in, prev_out, sig, ini, fcached = xs
        prev_m, prev_om = _take(prev_in, idx), _take(prev_out, idx)
        diff = jnp.sum(jnp.square(xm - prev_m), axis=(1, 2))
        cached = (fcached if force is not None else
                  (diff / (jnp.maximum(sig, 1e-30) * nd) <= thr) & ini)
        approx = a.gamma * lin(xm) + (1.0 - a.gamma) * prev_om
        out = jnp.where(cached[:, None, None], approx,
                        block(bp, xm, cs, d, quant))
        obs = diff / nd
        sig = jnp.where(cached, sig,
                        jnp.where(ini, a.momentum * sig
                                  + (1.0 - a.momentum) * obs, obs))
        new_in = _put(prev_in, idx, jnp.where(keep, xm, prev_m))
        return (out,), (new_in, sig, cached)

    (xm,), (new_in, sig, cached) = jax.lax.scan(
        body, (_take(x, idx),),
        (p["blocks"], st["hidden"][:-1], st["hidden"][1:], st["sigma2"],
         st["init"], forced))
    h = _put(static, idx, jnp.where(keep, xm, _take(static, idx)))
    new = {"tokens": x, "hidden": jnp.concatenate([new_in, h[None]]),
           "sigma2": sig, "init": jnp.ones_like(st["init"]),
           "skipped": st["skipped"] + jnp.sum(cached, axis=0)}
    return h, new


def rule_breaks(tok0, tok1, hid0, hid1, sig0, sig1, gated: bool, a: Algo,
                band: float = 1e-3) -> Tuple[int, int]:
    """Alg. 1's decisions at one cached step, applied to a program's own
    values before (``*0``) and after (``*1``) the step, against the
    decisions the program took: (breaks, rows that could not be read).

    tok (B, N, D) the step's tokens, hid (L+1, B, N, D) the blocks' inputs
    and the final hidden, sig (L, B) the variance trackers.  The motion
    tokens the program kept are those whose first block input changed; a
    block it cached kept its tracker.  A break is a token on the wrong side
    of the top-C / tau partition, a block cached or computed against the
    chi-square gate, or a tracker that does not follow the sliding window;
    a statistic within ``band`` (relative) of its boundary is rounding and
    breaks nothing.  ``gated`` is False on a request's second step, whose
    trackers see their first observation and cache nothing.  A row where
    fewer than C tokens were kept (a partition the program's state does not
    show) is counted as unread."""
    f = lambda v: np.asarray(v, np.float64)          # noqa: E731
    tok0, tok1, sig0, sig1 = f(tok0), f(tok1), f(sig0), f(sig1)
    nb, n = tok0.shape[:2]
    depth = sig0.shape[0]
    cap = max(1, int(round(a.capacity * n)))
    nd = cap * tok0.shape[-1]
    thr = gate_threshold(a.alpha, nd)
    near = lambda v, at: abs(v - at) <= band * abs(at)  # noqa: E731
    breaks = unread = 0
    for b in range(nb):
        sal = np.sum(np.square(tok1[b] - tok0[b]), axis=-1)
        order = np.argsort(-sal, kind="stable")
        want = np.zeros(n, bool)
        want[order[:cap]] = sal[order[:cap]] > a.tau
        kept = np.any(np.asarray(hid1[0, b]) != np.asarray(hid0[0, b]), -1)
        edge = [sal[order[cap - 1]], a.tau]
        if cap < n:
            edge.append(sal[order[cap]])
        breaks += sum(1 for t in np.flatnonzero(kept != want)
                      if not any(near(sal[t], e) for e in edge))
        if kept.sum() != cap:
            unread += 1
            continue
        idx = np.flatnonzero(kept)
        for layer in range(depth):
            diff = float(np.sum(np.square(f(hid1[layer, b, idx])
                                          - f(hid0[layer, b, idx]))))
            stat = diff / (max(sig0[layer, b], 1e-30) * nd)
            cached = sig1[layer, b] == sig0[layer, b]
            if cached != (gated and stat <= thr) and not near(stat, thr):
                breaks += 1
            if not cached:
                obs = diff / nd
                want_sig = (a.momentum * sig0[layer, b]
                            + (1.0 - a.momentum) * obs) if gated else obs
                if not near(sig1[layer, b], want_sig):
                    breaks += 1
    return breaks, unread


@functools.partial(jax.jit, static_argnames=("d", "a", "quant", "first"))
def guided_step(p, st, x, t, t_prev, labels, guidance, ac, force=None, *,
                d: Dims, a: Algo, quant: bool, first: bool):
    """One DDIM step of K requests under classifier-free guidance; the
    state holds 2K rows, conditional rows first.  ``force`` holds decisions
    to take as given: ``motion``, ``keep`` and ``cached`` (see
    ``fastcache_warm``), ``centres`` and ``assign`` (see ``merge``)."""
    k = x.shape[0]
    t2 = jnp.concatenate([t, t])
    lab = jnp.concatenate([labels, jnp.full((k,), d.classes, jnp.int32)])
    cs = conditioning(p, t2, lab, quant)
    tok = tokens_in(p, jnp.concatenate([x, x]), d, quant)
    st = dict(st)
    assign = None
    if a.merge_window:
        prev = tok if first else st["merge_prev"]
        st["merge_prev"] = tok
        tok, assign, _ = merge(tok, prev, a, quant, None if force is None
                               else force.get("centres"))
    if first or not a.fastcache:
        h, inputs = full_forward(p, tok, cs, d, quant)
        if a.fastcache:
            st.update(tokens=tok, hidden=jnp.concatenate([inputs, h[None]]))
    else:
        h, new = fastcache_warm(p, st, tok, cs, d, a, quant, None
                                if force is None else force.get("motion"))
        st.update(new)
    if assign is not None:
        h = unmerge(h, assign)
    eps = final_layer(p, h, cs, d, quant)
    eps_c, eps_u = eps[:k], eps[k:]
    g = guidance[:, None, None, None]
    eps = jnp.where(g == 1.0, eps_c, eps_u + g * (eps_c - eps_u))
    a_t = ac[t][:, None, None, None]
    a_prev = jnp.where(t_prev >= 0, ac[jnp.maximum(t_prev, 0)],
                       1.0)[:, None, None, None]
    x0 = (x - jnp.sqrt(1.0 - a_t) * eps) / jnp.sqrt(a_t)
    return jnp.sqrt(a_prev) * x0 + jnp.sqrt(1.0 - a_prev) * eps, st


def init_state(rows: int, d: Dims, a: Algo) -> Dict:
    n = d.tokens
    if a.merge_window:
        n = n // a.merge_window * min(a.merge_window, max(
            1, math.ceil(a.merge_ratio * a.merge_window)))
    if not a.fastcache:
        return {}
    return {"tokens": jnp.zeros((rows, n, d.hidden), F32),
            "hidden": jnp.zeros((d.depth + 1, rows, n, d.hidden), F32),
            "sigma2": jnp.ones((d.depth, rows), F32),
            "init": jnp.zeros((d.depth, rows), bool),
            "skipped": jnp.zeros((rows,), jnp.int32)}


def to_f32(params) -> Dict:
    return jax.tree.map(lambda v: jnp.asarray(v, F32), params)


def sample(p32: Dict, d: Dims, a: Algo, noise_seeds: Sequence[int],
           labels: Sequence[int], num_steps: int,
           guidance: Sequence[float], quant: bool = False
           ) -> Tuple[np.ndarray, np.ndarray]:
    """Sample K requests that share a step budget, each from its own noise.
    Returns (final latents, latents after the first step), each
    (K, side, side, C)."""
    ac = jnp.asarray(alphas_cumprod(), F32)
    ts, prev = ddim_timesteps(num_steps)
    k = len(noise_seeds)
    x = jnp.asarray(np.stack([request_noise(s, d) for s in noise_seeds]))
    lab = jnp.asarray(labels, jnp.int32)
    gui = jnp.asarray(guidance, F32)
    st = init_state(2 * k, d, a)
    first: Optional[np.ndarray] = None
    for i in range(num_steps):
        x, st = guided_step(p32, st, x, jnp.full((k,), ts[i], jnp.int32),
                            jnp.full((k,), prev[i], jnp.int32), lab, gui, ac,
                            d=d, a=a, quant=quant, first=i == 0)
        if i == 0:
            first = np.asarray(x)
    return np.asarray(x), first
