"""Fused saliency + Frobenius-delta Pallas kernel.

One pass over (X_t, X_{t-1}) produces the three reductions FastCache needs
per step (Eqs. 1 and 4): per-token squared-L2 saliency, ||X_t - X_{t-1}||_F^2
and ||X_{t-1}||_F^2 — replacing three separate HBM passes with one.

Grid: (N / BN, D / BD); the feature axis is the inner (minor) reduction axis,
so per-token partials accumulate in the (BN,) output block while the two
scalars accumulate across the whole grid (TPU grid execution is sequential,
revisited output blocks stay resident in VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _kernel(x_ref, xp_ref, sal_ref, diff_ref, prev_ref):
    j = pl.program_id(1)
    i = pl.program_id(0)
    x = x_ref[...].astype(F32)
    xp = xp_ref[...].astype(F32)
    d = x - xp
    part = jnp.sum(d * d, axis=1)                      # (BN,)

    @pl.when(j == 0)
    def _():
        sal_ref[...] = jnp.zeros_like(sal_ref)

    sal_ref[...] += part

    @pl.when((i == 0) & (j == 0))
    def _():
        diff_ref[...] = jnp.zeros_like(diff_ref)
        prev_ref[...] = jnp.zeros_like(prev_ref)

    diff_ref[...] += jnp.sum(part)[None, None]
    prev_ref[...] += jnp.sum(xp * xp)[None, None]


@functools.partial(jax.jit, static_argnames=("bn", "bd", "interpret"))
def saliency_delta(x: jax.Array, x_prev: jax.Array, *, bn: int = 128,
                   bd: int = 512, interpret: bool):
    """x, x_prev: (N, D) -> (saliency (N,), diff_sq (), prev_sq ())."""
    n, d = x.shape
    bn = min(bn, n)
    bd = min(bd, d)
    if n % bn or d % bd:
        raise ValueError(f"shape ({n},{d}) not divisible by block ({bn},{bd})")
    grid = (n // bn, d // bd)
    sal, diff, prev = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bd), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn,), lambda i, j: (i,)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), F32),
            jax.ShapeDtypeStruct((1, 1), F32),
            jax.ShapeDtypeStruct((1, 1), F32),
        ],
        interpret=interpret,
    )(x, x_prev)
    return sal, diff[0, 0], prev[0, 0]
