"""Pytree checkpoints: .npz arrays + msgpack tree spec. No orbax dependency;
roundtrip-safe for arbitrary nested dict/tuple pytrees including optimizer
NamedTuples (serialized structurally)."""
from __future__ import annotations

import json
import os
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _flatten(tree) -> Tuple[Dict[str, np.ndarray], Any]:
    paths_leaves, treedef = jax.tree.flatten_with_path(tree)
    arrays = {}
    keys = []
    for i, (path, leaf) in enumerate(paths_leaves):
        key = f"leaf_{i}"
        arrays[key] = np.asarray(leaf)
        keys.append(jax.tree_util.keystr(path))
    return arrays, (treedef, keys)


def save(path: str, tree, metadata: Dict | None = None) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    arrays, (treedef, keys) = _flatten(tree)
    np.savez(path if path.endswith(".npz") else path + ".npz", **arrays)
    meta = {
        "keys": keys,
        "treedef": str(treedef),
        "metadata": metadata or {},
        "num_leaves": len(keys),
    }
    with open(_meta_path(path), "w") as f:
        json.dump(meta, f)


def _meta_path(path: str) -> str:
    base = path[:-4] if path.endswith(".npz") else path
    return base + ".meta.json"


def load(path: str, like) -> Any:
    """Restore into the structure of `like` (a pytree of arrays or
    ShapeDtypeStructs)."""
    npz = np.load(path if path.endswith(".npz") else path + ".npz")
    leaves_like, treedef = jax.tree.flatten(like)
    n = len(leaves_like)
    if len(npz.files) != n:
        raise ValueError(f"checkpoint {path!r} holds {len(npz.files)} "
                         f"leaves; the target pytree expects {n}")
    leaves = []
    for i, ref in enumerate(leaves_like):
        arr = npz[f"leaf_{i}"]
        if tuple(arr.shape) != tuple(ref.shape):
            raise ValueError(f"checkpoint {path!r} leaf {i}: stored shape "
                             f"{tuple(arr.shape)} != expected "
                             f"{tuple(ref.shape)}")
        leaves.append(jnp.asarray(arr, dtype=ref.dtype))
    return jax.tree.unflatten(treedef, leaves)


def load_metadata(path: str) -> Dict:
    with open(_meta_path(path)) as f:
        return json.load(f)
