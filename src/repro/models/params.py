"""Parameter declaration: models describe params as a pytree of ``ParamDef``
(shape + logical axes + initializer); ``init_params`` materializes them with
per-leaf folded PRNG keys, and the same tree drives sharding-spec construction
(`repro.distributed.sharding.param_shardings`) and abstract dry-run inputs.
"""
from __future__ import annotations

import zlib
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class ParamDef(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"        # normal | zeros | ones | fan_in
    scale: float = 1.0
    dtype: Optional[str] = None  # override model default (e.g. f32 norms)


def _is_def(x) -> bool:
    return isinstance(x, ParamDef)


def init_params(defs, key: jax.Array, default_dtype: str):
    """Materialize a ParamDef tree. Key is folded per tree-path (order-stable)."""
    paths_defs, treedef = jax.tree.flatten_with_path(defs, is_leaf=_is_def)

    leaves = []
    for path, d in paths_defs:
        if len(d.shape) != len(d.axes):
            raise ValueError(
                f"ParamDef at {jax.tree_util.keystr(path)}: shape {d.shape} "
                f"has {len(d.shape)} dims but axes {d.axes} names "
                f"{len(d.axes)}")
        dtype = jnp.dtype(d.dtype or default_dtype)
        k = jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path).encode()))
        if d.init == "zeros":
            leaf = jnp.zeros(d.shape, dtype)
        elif d.init == "ones":
            leaf = jnp.ones(d.shape, dtype)
        elif d.init == "fan_in":
            fan_in = d.shape[-2] if len(d.shape) >= 2 else d.shape[-1]
            std = d.scale / (fan_in ** 0.5)
            leaf = (jax.random.normal(k, d.shape, jnp.float32) * std).astype(dtype)
        else:  # normal
            leaf = (jax.random.normal(k, d.shape, jnp.float32)
                    * 0.02 * d.scale).astype(dtype)
        leaves.append(leaf)
    return jax.tree.unflatten(treedef, leaves)


def abstract_params(defs, default_dtype: str):
    """ShapeDtypeStruct tree matching ``init_params`` output (for dry-run)."""
    def one(d: ParamDef):
        return jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype or default_dtype))
    return jax.tree.map(one, defs, is_leaf=_is_def)


def count_params(defs) -> int:
    total = 0
    for d in jax.tree.leaves(defs, is_leaf=_is_def):
        n = 1
        for s in d.shape:
            n *= s
        total += n
    return total
