"""Flash attention (online softmax) Pallas kernel: causal, sliding-window,
GQA.  TPU tiling: grid (B, H, Sq/BQ, Skv/BK) with the KV axis minor; the
(BQ, dh) f32 accumulator plus (BQ, 1) running max / denominator live in VMEM
scratch across KV steps.  Causal block-skipping uses @pl.when — fully-masked
KV blocks issue no MXU work on TPU (this is the kernel that removes the
masked-FLOP waste of the XLA fallback path, see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, bq: int, bk: int, nk: int,
            q_offset: int, scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq + q_offset
    k_start = kj * bk

    # live unless the whole KV block is masked out
    live = jnp.bool_(True)
    if causal:
        live &= k_start <= q_start + bq - 1
    if window > 0:
        live &= k_start + bk - 1 > q_start - window

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(F32)                    # (BQ, dh)
        k = k_ref[0, 0].astype(F32)                    # (BK, dh)
        v = v_ref[0, 0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                            # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(kj == nk - 1)
    def _():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, bq: int = 128,
                    bk: int = 128, interpret: bool) -> jax.Array:
    """q: (B, H, Sq, dh); k, v: (B, KVH, Skv, dh). GQA via head grouping;
    query positions are aligned to the END of the KV sequence."""
    b, h, sq, dh = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    g = h // kvh
    bq = min(bq, sq)
    bk = min(bk, skv)
    if sq % bq or skv % bk:
        raise ValueError(f"(Sq={sq}, Skv={skv}) not divisible by ({bq},{bk})")
    nk = skv // bk
    grid = (b, h, sq // bq, nk)
    kernel = functools.partial(
        _kernel, causal=causal, window=window, bq=bq, bk=bk, nk=nk,
        q_offset=skv - sq, scale=dh ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, qi, kj: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, qi, kj, g=g: (b_, h_ // g, kj, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, qi, kj, g=g: (b_, h_ // g, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, qi, kj: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), F32),
            pltpu.VMEM((bq, 1), F32),
            pltpu.VMEM((bq, dh), F32),
        ],
        interpret=interpret,
    )(q, k, v)
