"""Fused linear-approximation + motion-aware-blend Pallas kernel.

The FastCache hot path when a block is cached (Eqs. 3/6 + MB):

    out = gamma * (X @ W + b) + (1 - gamma) * prev

One MXU-tiled GEMM with the bias add and blend fused into the epilogue —
no (M, F) intermediate ever hits HBM.  Grid (M/BM, F/BF, D/BK); the K axis is
minor so the f32 accumulator block stays resident in VMEM across K steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32


def _kernel(x_ref, w_ref, b_ref, prev_ref, out_ref, *, gamma: float,
            nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(x_ref[...].astype(F32), w_ref[...].astype(F32),
                            preferred_element_type=F32)

    @pl.when(k == nk - 1)
    def _():
        acc = out_ref[...] + b_ref[...].astype(F32)
        out_ref[...] = gamma * acc + (1.0 - gamma) * prev_ref[...].astype(F32)


@functools.partial(jax.jit,
                   static_argnames=("gamma", "bm", "bf", "bk", "interpret"))
def linear_blend(x: jax.Array, w: jax.Array, b: jax.Array, prev: jax.Array,
                 *, gamma: float = 0.5, bm: int = 128, bf: int = 256,
                 bk: int = 256, interpret: bool) -> jax.Array:
    """x: (M, D); w: (D, F); b: (F,); prev: (M, F) -> (M, F) in f32."""
    m, d = x.shape
    f = w.shape[1]
    bm, bf, bk = min(bm, m), min(bf, f), min(bk, d)
    if m % bm or f % bf or d % bk:
        raise ValueError(f"({m},{d},{f}) not divisible by ({bm},{bk},{bf})")
    nk = d // bk
    out = pl.pallas_call(
        functools.partial(_kernel, gamma=gamma, nk=nk),
        grid=(m // bm, f // bf, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bf), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bf), lambda i, j, k: (0, j)),
            pl.BlockSpec((bm, bf), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, f), F32),
        interpret=interpret,
    )(x, w, b.reshape(1, f), prev)
    return out.astype(x.dtype)
