"""TokenReducer: the serving-path token-compression stage (CTM, Eqs. 10-13).

One reducer sits between ``tokens_in`` and the cache policy inside
``CachedDiT.step``: per sample and per step it scores tokens (kNN density x
temporal motion), merges each fixed window of ``w`` tokens down to a STATIC
M = ceil(r * w) cluster centers (``core/token_merge.py``; the fused Pallas
kernels in ``kernels/token_merge.py`` back the TPU hot path), hands the
policy the reduced (B, M_total, D) grid, and unmerges the final hidden back
to full resolution inside the policy's ``_eps`` — so every registered cache
policy composes with token compression without knowing it exists.

Static-shape contract (the jit/serving requirement): M is computed at
construction time from (window, keep_ratio), so the reduced grid never
changes shape across steps, samples, or admissions — capacity overflow
(a ratio that rounds up to the full window) degrades speed, never shape,
by deactivating the reducer entirely (``active == False`` => the runner
drops it and the step is bitwise-identical to merge-off).

Per-sample state: the previous step's full-resolution tokens (the temporal
term of Eq. 12) ride the policy state pytree under the reserved ``tokred``
key — (B, N, D) + a (B,) warm flag, so the sharding walker places them over
the mesh ``data`` axis and engine admissions reset them per slot like any
cache payload.  A cold row scores against itself (zero motion), keeping
every row's merge decision independent of its batchmates — the engines'
bitwise mid-flight-admission contract.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import token_merge
from repro.models.dit import DiTModel

F32 = jnp.float32

# the reserved key the reducer's rows ride under in the policy state pytree
STATE_KEY = "tokred"


class TokenReducer:
    def __init__(self, model: DiTModel, fc, *, use_fused: bool = False):
        self.window = int(fc.merge_window)
        self.keep_ratio = float(fc.merge_ratio)
        self.k = int(fc.knn_k)
        self.lam = float(fc.merge_lambda)
        self.use_fused = use_fused
        self.n_tokens = model.num_tokens
        self.d_model = model.cfg.d_model
        self.dtype = jnp.dtype(model.cfg.dtype)
        if self.window < 2:
            raise ValueError(f"merge_window must be >= 2, got {self.window}")
        self.m = token_merge.keep_count(self.window, self.keep_ratio)
        # a ratio whose ceil hits the full window keeps every token: the
        # stage is statically inert (the runner drops the reducer, so
        # r=1.0 is bitwise-identical to merge-off, not just allclose)
        self.active = self.m < self.window
        if self.active:
            if self.n_tokens % self.window != 0:
                raise ValueError(
                    f"token count {self.n_tokens} must be divisible by the "
                    f"merge window {self.window}")
            token_merge._check_k(self.k, self.window)
        self.n_windows = self.n_tokens // max(1, self.window)
        self.reduced_tokens = (self.n_windows * self.m if self.active
                               else self.n_tokens)
        self._mm = None                 # per-trace MergeMap stash (see step)

    # -- per-sample state (rides the policy pytree under STATE_KEY) ------

    def init_rows(self, batch: int) -> Dict[str, jax.Array]:
        return {
            "prev_full": jnp.zeros((batch, self.n_tokens, self.d_model),
                                   self.dtype),
            "have_prev": jnp.zeros((batch,), bool),
        }

    def reset_rows(self, tr: Dict, rows) -> Dict[str, jax.Array]:
        return {
            "prev_full": tr["prev_full"].at[rows].set(0.0),
            "have_prev": tr["have_prev"].at[rows].set(False),
        }

    # -- the stage -------------------------------------------------------

    def reduce(self, x_full: jax.Array, tr: Dict
               ) -> Tuple[jax.Array, Dict]:
        """(B, N, D) full-resolution tokens -> (B, M_total, D) merged grid
        + refreshed reducer rows.  The MergeMap is stashed on the reducer
        for THIS trace only — ``unmerge`` (called from the policy's
        ``_eps`` later in the same traced step) consumes it, and the
        runner clears it when the step returns."""
        with jax.named_scope("merge"):
            prev = jnp.where(tr["have_prev"][:, None, None],
                             tr["prev_full"].astype(x_full.dtype), x_full)
            merged, mm = token_merge.merge_tokens(
                x_full, prev, window=self.window,
                keep_ratio=self.keep_ratio, k=self.k, lam=self.lam,
                use_fused=self.use_fused)
        self._mm = mm
        new_tr = {"prev_full": x_full.astype(self.dtype),
                  "have_prev": jnp.ones_like(tr["have_prev"])}
        return merged, new_tr

    def unmerge(self, hidden: jax.Array) -> jax.Array:
        """(B, M_total, D) reduced hidden -> (B, N, D) via the step's
        stashed assignment (Alg. 2's M mapping)."""
        if self._mm is None:
            raise RuntimeError("TokenReducer.unmerge called outside a "
                               "reduce()d step (no MergeMap stashed)")
        with jax.named_scope("unmerge"):
            return token_merge.unmerge_tokens(
                hidden, self._mm, window=self.window,
                n_tokens=self.n_tokens, use_fused=self.use_fused)
