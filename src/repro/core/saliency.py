"""Spatial-temporal token saliency + static/motion partition (Eqs. 1-3).

TPU adaptation (DESIGN.md §3): the paper's threshold split produces ragged
shapes; here the motion set has a *static capacity* C = ceil(r * N).  Tokens
are ranked by temporal saliency; the top-C that also exceed tau_s are motion,
everything else takes the learnable-linear bypass.  Capacity overflow sends
would-be-motion tokens to the *cheap* path, degrading speed never shape.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def token_saliency(x_t: jax.Array, x_prev: jax.Array) -> jax.Array:
    """Eq. 1: per-token squared L2 temporal difference. (B,N,D) -> (B,N)."""
    d = (x_t.astype(F32) - x_prev.astype(F32))
    return jnp.sum(d * d, axis=-1)


class Partition(NamedTuple):
    motion_idx: jax.Array    # (B, C) token indices, saliency-descending
    is_motion: jax.Array     # (B, N) bool — in top-C AND above tau_s
    saliency: jax.Array      # (B, N)


def partition_tokens(saliency: jax.Array, tau_s: float,
                     capacity: int) -> Partition:
    """Select motion tokens: top-`capacity` by saliency, gated by tau_s."""
    n = saliency.shape[-1]
    capacity = min(capacity, n)
    _, idx = jax.lax.top_k(saliency, capacity)              # (B, C)
    above = jnp.take_along_axis(saliency, idx, axis=-1) > tau_s
    is_motion = jnp.zeros(saliency.shape, bool).at[
        jnp.arange(saliency.shape[0])[:, None], idx].set(above)
    return Partition(motion_idx=idx, is_motion=is_motion, saliency=saliency)


def gather_motion(x: jax.Array, part: Partition) -> jax.Array:
    """(B,N,D) -> (B,C,D) motion-token stream (saliency-descending order)."""
    return jnp.take_along_axis(x, part.motion_idx[..., None], axis=1,
                               mode="promise_in_bounds")


def scatter_motion(base: jax.Array, motion: jax.Array,
                   part: Partition) -> jax.Array:
    """Write the motion stream back over `base` at its token positions,
    but only where the tau_s gate marked the token as true motion."""
    b = base.shape[0]
    keep = motion_keep(part)
    updated = base.at[jnp.arange(b)[:, None], part.motion_idx].set(
        jnp.where(keep[..., None], motion,
                  jnp.take_along_axis(base, part.motion_idx[..., None],
                                      axis=1)))
    return updated


def motion_keep(part: Partition) -> jax.Array:
    """(B, C) bool: which of the top-C tokens the tau_s gate kept as
    motion, in ``motion_idx`` order."""
    return jnp.take_along_axis(part.is_motion, part.motion_idx, axis=-1)


def gather_motion_layer(stack: jax.Array, layer,
                        part: Partition) -> jax.Array:
    """(K,B,N,D) -> (B,C,D): the motion tokens of layer ``layer`` of a
    layer stack."""
    return gather_motion(
        jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False), part)


def set_motion_layer(stack: jax.Array, layer, rows: jax.Array,
                     part: Partition) -> jax.Array:
    """Write (B,C,D) ``rows`` over layer ``layer`` of a (K,B,N,D) stack at
    the motion tokens' positions, leaving every other token untouched.

    The layer is sliced out, scattered and written back whole: on a TPU
    v5e a scatter of rows straight into the stack in HBM runs row by row,
    over 3x slower than slicing the layer into fast memory, scattering
    there and writing it back.  Mapped over the batch, so a stack sharded
    on it is written shard by shard."""
    cur = jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    cur = jax.vmap(lambda c, i, r: c.at[i].set(r, unique_indices=True))(
        cur, part.motion_idx, rows.astype(stack.dtype))
    return jax.lax.dynamic_update_index_in_dim(stack, cur, layer, 0)


def motion_fraction(part: Partition) -> jax.Array:
    """Per-sample fraction of tokens marked motion. (B, N) -> (B,)."""
    return jnp.mean(part.is_motion.astype(F32), axis=-1)
