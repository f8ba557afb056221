"""Fused per-sample cache-gate Pallas kernel.

One pass over a layer's motion-stream hiddens fuses the four stages of the
FastCache block decision (Eqs. 4-7 + Eq. 6/MB) *per batch sample*:

    saliency delta   diff_b = ||X_b - Xprev_b||_F^2
    chi^2 statistic  stat_b = diff_b / (sigma2_b * ND)
    gate             g_b    = (stat_b <= chi2_{ND,1-a}/ND) & eligible_b
    linear blend     out_b  = g_b ? gamma*(X_b W + c) + (1-gamma)*prev_out_b
                                  : X_b

The non-gated samples pass through unchanged and are overwritten by the real
transformer block outside the kernel; the gated samples never leave VMEM
between the reduction and the blend.

Grid: (B, 2, C/BC) — for each sample the phase axis makes two passes over the
token blocks: phase 0 accumulates the Frobenius reductions into the (1, 1)
scalar outputs (TPU grid execution is sequential, so revisited output blocks
stay resident in VMEM); phase 1 reads the finished statistic, decides the
gate, and writes the blended output tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _kernel(x_ref, xp_ref, po_ref, w_ref, b_ref, sig_ref, elig_ref,
            out_ref, gate_ref, diff_ref, prev_ref, *, nd: int,
            threshold: float, gamma: float, use_blend: bool):
    p = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((p == 0) & (j == 0))
    def _():
        diff_ref[...] = jnp.zeros_like(diff_ref)
        prev_ref[...] = jnp.zeros_like(prev_ref)

    @pl.when(p == 0)
    def _():
        x = x_ref[0].astype(F32)                       # (BC, D)
        xp = xp_ref[0].astype(F32)
        d = x - xp
        diff_ref[...] += jnp.sum(d * d).reshape(1, 1, 1)
        prev_ref[...] += jnp.sum(xp * xp).reshape(1, 1, 1)

    @pl.when(p == 1)
    def _():
        stat = diff_ref[0, 0, 0] / (jnp.maximum(sig_ref[0, 0, 0], 1e-30) * nd)
        g = (stat <= threshold) & (elig_ref[0, 0, 0] > 0.0)

        @pl.when(j == 0)
        def _():
            gate_ref[...] = jnp.where(g, 1.0, 0.0).reshape(1, 1, 1)

        # non-gated samples pass through and are overwritten by the real
        # block outside the kernel — skip their MXU work entirely
        @pl.when(g)
        def _():
            x = x_ref[0].astype(F32)
            approx = jnp.dot(x, w_ref[...].astype(F32),
                             preferred_element_type=F32) \
                + b_ref[...].astype(F32)
            if use_blend:
                approx = gamma * approx + (1.0 - gamma) * po_ref[0].astype(F32)
            out_ref[...] = approx[None]

        @pl.when(jnp.logical_not(g))
        def _():
            out_ref[...] = x_ref[0].astype(F32)[None]


@functools.partial(jax.jit, static_argnames=("threshold", "gamma",
                                             "use_blend", "bc", "interpret"))
def fused_gate(x: jax.Array, prev_in: jax.Array, prev_out: jax.Array,
               w: jax.Array, b: jax.Array, sigma2: jax.Array,
               eligible: jax.Array, *, threshold: float, gamma: float = 0.5,
               use_blend: bool = True, bc: int = 0, interpret: bool):
    """x, prev_in, prev_out: (B, C, D); w: (D, D); b: (D,);
    sigma2, eligible: (B,).  Returns (out (B,C,D) in x.dtype, gate (B,) bool,
    diff_sq (B,) f32, prev_sq (B,) f32)."""
    bsz, c, d = x.shape
    bc = min(bc or c, c)
    if c % bc:
        raise ValueError(f"motion length {c} not divisible by block {bc}")
    nd = c * d
    # per-sample scalars ride as (B, 1, 1): a (1, 1, 1) block then equals
    # the array's last two dims, which the TPU block-shape rule accepts
    sig = sigma2.astype(F32).reshape(bsz, 1, 1)
    elig = eligible.astype(F32).reshape(bsz, 1, 1)
    grid = (bsz, 2, c // bc)
    out, gate, diff, prevsq = pl.pallas_call(
        functools.partial(_kernel, nd=nd, threshold=threshold, gamma=gamma,
                          use_blend=use_blend),
        name="fused_gate",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda i, p, j: (i, j, 0)),
            pl.BlockSpec((1, bc, d), lambda i, p, j: (i, j, 0)),
            pl.BlockSpec((1, bc, d), lambda i, p, j: (i, j, 0)),
            pl.BlockSpec((d, d), lambda i, p, j: (0, 0)),
            pl.BlockSpec((1, d), lambda i, p, j: (0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, p, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, p, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bc, d), lambda i, p, j: (i, j, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, p, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, p, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda i, p, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, c, d), F32),
            jax.ShapeDtypeStruct((bsz, 1, 1), F32),
            jax.ShapeDtypeStruct((bsz, 1, 1), F32),
            jax.ShapeDtypeStruct((bsz, 1, 1), F32),
        ],
        interpret=interpret,
    )(x, prev_in, prev_out, w, b.reshape(1, d), sig, elig)
    return (out.astype(x.dtype), gate[:, 0, 0] > 0.0, diff[:, 0, 0],
            prevsq[:, 0, 0])
