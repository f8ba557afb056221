"""Local-window kNN token density Pallas kernel (Eq. 10; CTM stage 1).

Each grid step loads one window of w tokens into VMEM, forms the (w, w)
pairwise squared-distance matrix (one MXU (w,D)x(D,w) matmul + rank-1 terms),
then extracts the K smallest off-diagonal distances per row by K rounds of
masked-min (K <= 10, unrolled) — no sort, no gather.  rho_sp = exp(-mean_K).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

F32 = jnp.float32
INF = jnp.inf


def _kernel(h_ref, out_ref, *, k: int, w: int, d: int):
    h = h_ref[0].astype(F32)                               # (w, D)
    sq = jnp.sum(h * h, axis=1, keepdims=True)             # (w, 1)
    g = jax.lax.dot_general(h, h, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)    # (w, w)
    ii = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
    # the squared norms as a row: the diagonal of the Gram matrix, summed
    # down its columns (no in-kernel transpose)
    sq_row = jnp.sum(jnp.where(ii == jj, g, 0.0), axis=0, keepdims=True)
    dist = jnp.maximum(sq + sq_row - 2.0 * g, 0.0)
    dist = jnp.where(ii == jj, INF, dist)
    # the distance matrix is symmetric, so column j holds token j's
    # distances: reducing down the sublanes yields a (1, w) row per round
    acc = jnp.zeros((1, w), F32)
    for _ in range(k):                                     # unrolled K-min
        mn = jnp.min(dist, axis=0, keepdims=True)          # (1, w)
        acc = acc + mn
        # mask exactly one argmin occurrence per column (the first)
        first = jnp.min(jnp.where(dist == mn, ii, w), axis=0, keepdims=True)
        dist = jnp.where(ii == first, INF, dist)
    out_ref[0] = jnp.exp(-acc / (k * d))   # per-dim normalized (see ref.py)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def knn_density(h: jax.Array, *, k: int = 5,
                interpret: bool) -> jax.Array:
    """h: (n_windows, w, D) -> rho_sp (n_windows, w)."""
    nw, w, d = h.shape
    if not 1 <= k <= w - 1:
        # identical validation to kernels/ref.py and core/token_merge —
        # the static-k unroll below must never silently diverge from the
        # k the caller asked for (the pre-fix clamp did exactly that)
        raise ValueError(f"knn_density k={k} out of range for window "
                         f"w={w}; need 1 <= k <= w-1 = {w - 1}")
    return pl.pallas_call(
        functools.partial(_kernel, k=k, w=w, d=d),
        name="knn_density",
        grid=(nw,),
        in_specs=[pl.BlockSpec((1, w, d), lambda i: (i, 0, 0))],
        # (nw, 1, w): a (1, 1, w) block equals the array's last two dims,
        # which the TPU block-shape rule accepts
        out_specs=pl.BlockSpec((1, 1, w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nw, 1, w), F32),
        interpret=interpret,
    )(h).reshape(nw, w)
