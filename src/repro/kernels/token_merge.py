"""Fused token-merge Pallas kernels (CTM stages 2-3; Eqs. 12-13, Alg. 2).

``merge_assign`` fuses center selection -> nearest-center assignment ->
importance-weighted cluster means for one window per grid step, entirely in
VMEM: M unrolled masked-max rounds pick the top-M scored tokens as centers
(same first-occurrence tie-break as ``lax.top_k``), one (w, M) distance
matrix assigns every token to its nearest center (first-occurrence argmin),
and two MXU matmuls produce the merged (M, D) cluster means — no sort, no
gather, mirroring the masked-min idiom of ``knn_density.py``.

``unmerge_scatter`` restores the window: a one-hot (w, M) assignment matmul
replicates each cluster representative back to every member token (the
gather-as-matmul form the MXU wants; exact, since each row selects one
element).

Pure-jnp twins with the same names live in ``kernels/ref.py``; interpret-mode
parity is pinned by tests/test_kernels.py per the reprolint kernel-parity
rule.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
NEG_INF = -jnp.inf
# one-hot selections must copy f32 values exactly, not through one bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST


def _merge_kernel(h_ref, s_ref, merged_ref, assign_ref, centers_ref, *,
                  m: int, w: int, d: int):
    h = h_ref[0].astype(F32)                               # (w, D)
    s = s_ref[0].astype(F32)                               # (1, w)
    jj = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
    im = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    ii_mw = jax.lax.broadcasted_iota(jnp.int32, (m, w), 0)

    # ---- top-M centers by score: M unrolled masked-max rounds, each
    # taking the first occurrence of the maximum (ties resolve to the lower
    # index, matching lax.top_k's stable ordering in ref.merge_assign)
    sc = s
    sel_mat = jnp.zeros((m, w), F32)
    centers = jnp.zeros((m, 1), jnp.int32)
    for r in range(m):
        mx = jnp.max(sc, axis=1, keepdims=True)            # (1, 1)
        first = jnp.min(jnp.where(sc == mx, jj, w), axis=1,
                        keepdims=True)                     # (1, 1)
        sel = jj == first                                  # (1, w) one-hot
        sel_mat = jnp.where(ii_mw == r, sel.astype(F32), sel_mat)
        centers = jnp.where(im == r, first, centers)
        sc = jnp.where(sel, NEG_INF, sc)
    centers_ref[0] = centers                               # (M, 1)

    # ---- nearest-center assignment on the transposed (M, w) distance
    # matrix: reducing down the sublanes gives each token's first-occurrence
    # argmin (matching jnp.argmin) as a (1, w) row, with no transpose
    ch = jax.lax.dot_general(sel_mat, h, (((1,), (0,)), ((), ())),
                             preferred_element_type=F32,
                             precision=HIGHEST)            # (M, D)
    csq = jnp.sum(ch * ch, axis=1, keepdims=True)          # (M, 1)
    hsq = jax.lax.dot_general(jnp.ones((1, d), F32), h * h,
                              (((1,), (1,)), ((), ())),
                              preferred_element_type=F32,
                              precision=HIGHEST)           # (1, w)
    d2 = (csq + hsq
          - 2.0 * jax.lax.dot_general(ch, h, (((1,), (1,)), ((), ())),
                                      preferred_element_type=F32,
                                      precision=HIGHEST))  # (M, w)
    mn = jnp.min(d2, axis=0, keepdims=True)                # (1, w)
    assign = jnp.min(jnp.where(d2 == mn, ii_mw, m), axis=0,
                     keepdims=True)                        # (1, w)
    assign_ref[0] = assign

    # ---- importance-weighted cluster means (Eq. 13)
    wgt = jnp.where(ii_mw == assign, s, 0.0)               # (M, w)
    num = jax.lax.dot_general(wgt, h, (((1,), (0,)), ((), ())),
                              preferred_element_type=F32,
                              precision=HIGHEST)           # (M, D)
    den = jnp.maximum(jnp.sum(wgt, axis=1, keepdims=True), 1e-9)  # (M, 1)
    merged_ref[0] = (num / den).astype(merged_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "interpret"))
def merge_assign(h: jax.Array, s: jax.Array, *, m: int,
                 interpret: bool):
    """h: (W, w, D) windowed tokens, s: (W, w) per-window-normalized
    importance -> (merged (W, M, D), assign (W, w) int32, centers (W, M)
    int32) with M = ``m`` static centers per window."""
    nw, w, d = h.shape
    if not 1 <= m <= w:
        raise ValueError(f"merge_assign m={m} out of range for window "
                         f"w={w}; need 1 <= m <= w")
    # per-window rows ride as (nw, 1, w) and columns as (nw, m, 1): each
    # block then equals its array's last two dims, which the TPU
    # block-shape rule accepts
    merged, assign, centers = pl.pallas_call(
        functools.partial(_merge_kernel, m=m, w=w, d=d),
        name="merge_assign",
        grid=(nw,),
        in_specs=[pl.BlockSpec((1, w, d), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, 1, w), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((1, m, d), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, w), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, m, 1), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nw, m, d), h.dtype),
                   jax.ShapeDtypeStruct((nw, 1, w), jnp.int32),
                   jax.ShapeDtypeStruct((nw, m, 1), jnp.int32)],
        interpret=interpret,
    )(h, s.reshape(nw, 1, w))
    return merged, assign.reshape(nw, w), centers.reshape(nw, m)


def _unmerge_kernel(merged_ref, assign_ref, out_ref, *, m: int, w: int,
                    d: int):
    mg = merged_ref[0].astype(F32)                         # (M, D)
    a = assign_ref[0]                                      # (w, 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, (w, m), 1)
    onehot = (a == jj).astype(F32)                         # (w, M)
    out = jax.lax.dot_general(onehot, mg, (((1,), (0,)), ((), ())),
                              preferred_element_type=F32,
                              precision=HIGHEST)           # (w, D)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unmerge_scatter(merged: jax.Array, assign: jax.Array, *,
                    interpret: bool) -> jax.Array:
    """merged: (W, M, D) cluster means, assign: (W, w) int32 ->
    (W, w, D): every token takes its cluster representative."""
    nw, m, d = merged.shape
    w = assign.shape[1]
    return pl.pallas_call(
        functools.partial(_unmerge_kernel, m=m, w=w, d=d),
        name="unmerge_scatter",
        grid=(nw,),
        # the assignment rides as an (nw, w, 1) column so the one-hot is
        # built without an in-kernel transpose; the block equals the
        # array's last two dims, which the TPU block-shape rule accepts
        in_specs=[pl.BlockSpec((1, m, d), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, w, 1), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, w, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nw, w, d), merged.dtype),
        interpret=interpret,
    )(merged, assign.reshape(nw, w, 1))
