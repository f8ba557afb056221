"""Seeded DiT weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the system under test and
the plain reference (``bench/reference.py``) read the same numbers and
neither takes them from the other.  The pytree follows the serving
program's parameter layout (``dims`` names every size):

    patch_w (p*p*C, D)  patch_b (D,)  pos_emb (N, D)
    t_w1 (256, D)  t_b1 (D,)  t_w2 (D, D)  t_b2 (D,)
    label_emb (classes + 1, D)          the last row is the null label
    blocks: ada_w (L, D, 6D)  ada_b (L, 6D)
            wq, wk, wv (L, D, H, dh)  wo (L, H, dh, D)
            w_in (L, D, F)  b_in (L, F)  w_out (L, F, D)  b_out (L, D)
    final_ada_w (D, 2D)  final_ada_b (2D,)  final_w (D, out)  final_b (out,)

with ``out = p*p*C`` (eps), doubled under ``learn_sigma`` (eps first, then
the variance channels, each laid out (row, col, channel) per patch).

Trained DiT weights are not in the repository.  These stand-ins keep every
activation at unit scale: projections are normal with std 1/sqrt(fan_in),
the adaLN modulation is small and non-zero (a zero-initialised DiT block is
the identity, and every cache would be exact), and the positional
embedding is DiT's fixed 2-D sine-cosine table.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Dims(NamedTuple):
    depth: int
    hidden: int
    heads: int
    mlp: int
    patch: int
    channels: int
    grid: int          # latent side / patch
    classes: int
    out: int           # final projection width

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads

    @property
    def tokens(self) -> int:
        return self.grid * self.grid

    @property
    def patch_dim(self) -> int:
        return self.patch * self.patch * self.channels


def dims_of(cfg: Dict) -> Dims:
    """Sizes from a configuration file (published DiT key names)."""
    p, c = int(cfg["patch_size"]), int(cfg["in_channels"])
    return Dims(depth=int(cfg["depth"]), hidden=int(cfg["hidden_size"]),
                heads=int(cfg["num_heads"]),
                mlp=int(round(cfg["mlp_ratio"] * cfg["hidden_size"])),
                patch=p, channels=c, grid=int(cfg["input_size"]) // p,
                classes=int(cfg["num_classes"]),
                out=p * p * c * (2 if cfg["learn_sigma"] else 1))


def seed_key(seed: int, *tags: int) -> jax.Array:
    """A PRNG key from any non-negative seed (wider than 32 bits too),
    folded with ``tags`` so that each use of one seed draws its own
    stream."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed % 2**31)
    key = jax.random.fold_in(key, (seed >> 31) % 2**31)
    for tag in tags:
        key = jax.random.fold_in(key, tag)
    return key


def sincos_pos_embed(hidden: int, grid: int) -> jax.Array:
    """DiT's fixed 2-D sine-cosine positional embedding, (grid*grid, D):
    the first half of the channels encodes the row, the second the
    column, each as [sin, cos] over D/4 frequencies."""
    quarter = hidden // 4
    omega = 1.0 / 10000 ** (jnp.arange(quarter, dtype=jnp.float32) / quarter)
    pos = jnp.arange(grid, dtype=jnp.float32)
    rows = jnp.repeat(pos, grid)[:, None] * omega[None]
    cols = jnp.tile(pos, grid)[:, None] * omega[None]
    return jnp.concatenate([jnp.sin(rows), jnp.cos(rows),
                            jnp.sin(cols), jnp.cos(cols)], axis=1)


def _make(key: jax.Array, d: Dims, dtype) -> Dict:
    D, L, H, dh, F = d.hidden, d.depth, d.heads, d.head_dim, d.mlp
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std):
        return (std * jax.random.normal(next(keys), shape, jnp.float32)
                ).astype(dtype)

    def zeros(shape):
        return jnp.zeros(shape, dtype)

    blocks = {
        "ada_w": normal((L, D, 6 * D), 0.05),
        "ada_b": normal((L, 6 * D), 0.2),
        "wq": normal((L, D, H, dh), D ** -0.5),
        "wk": normal((L, D, H, dh), D ** -0.5),
        "wv": normal((L, D, H, dh), D ** -0.5),
        "wo": normal((L, H, dh, D), D ** -0.5),
        "w_in": normal((L, D, F), D ** -0.5),
        "b_in": zeros((L, F)),
        "w_out": normal((L, F, D), F ** -0.5),
        "b_out": zeros((L, D)),
    }
    return {
        "patch_w": normal((d.patch_dim, D), d.patch_dim ** -0.5),
        "patch_b": zeros((D,)),
        "pos_emb": sincos_pos_embed(D, d.grid).astype(dtype),
        "t_w1": normal((256, D), 256 ** -0.5),
        "t_b1": zeros((D,)),
        "t_w2": normal((D, D), D ** -0.5),
        "t_b2": zeros((D,)),
        "label_emb": normal((d.classes + 1, D), 0.02),
        "blocks": blocks,
        "final_ada_w": normal((D, 2 * D), 0.05),
        "final_ada_b": zeros((2 * D,)),
        "final_w": normal((D, d.out), D ** -0.5),
        "final_b": zeros((d.out,)),
    }


@functools.lru_cache(maxsize=None)
def _maker(d: Dims, dtype: str):
    return jax.jit(functools.partial(_make, d=d, dtype=jnp.dtype(dtype)))


def make_params(d: Dims, seed: int, dtype: str = "bfloat16") -> Dict:
    """The whole pytree from ``seed``, on the default device, in ``dtype``."""
    return _maker(d, dtype)(seed_key(seed, 0))


def request_noise(noise_seed: int, d: Dims) -> np.ndarray:
    """A request's initial latents, (side, side, C) float32: a standard
    normal draw keyed by the request's noise seed (the serving engine's
    documented convention, ``PRNGKey(seed)``)."""
    side = d.grid * d.patch
    return np.asarray(jax.random.normal(jax.random.PRNGKey(noise_seed),
                                        (side, side, d.channels),
                                        jnp.float32))
