"""Operations and bytes that the served work needs, from shapes.

FLOPs count the multiply-adds of matrix products, two per product; the
elementwise work (norms, softmax, the gate's statistics) is left out, as
is usual for a utilisation.  ``D`` is the hidden size, ``F`` the MLP
width, ``n`` the tokens a block sees.

Bytes are what a kernel has to move through HBM at the least: its
operands read once and its results written once.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

from bench.weights import Dims


def block(n: int, D: int, F: int) -> float:
    """One DiT block on n tokens of one row: adaLN modulation (per row),
    q/k/v and output projections, attention scores and values over n
    keys, and the MLP."""
    return 12 * D * D + 8 * n * D * D + 4 * n * n * D + 4 * n * D * F


def embed_and_final(d: Dims) -> float:
    """Patch embedding, timestep MLP and the final layer, per row."""
    D, N = d.hidden, d.tokens
    return (2 * N * d.patch_dim * D + 2 * 256 * D + 2 * D * D
            + 4 * D * D + 2 * N * D * d.out)


def merge(N: int, w: int, m: int, D: int) -> float:
    """kNN Gram matrices in windows, centre distances and the weighted
    means, per row."""
    return 2 * N * w * D + 4 * N * m * D


class Shape(NamedTuple):
    """The serving shapes of a configuration."""
    d: Dims
    n: int            # tokens the policy sees (after merging)
    motion: int       # fastcache's motion tokens
    window: int       # merge window (0: no merging)
    centres: int      # merge centres per window


def shape_of(d: Dims, algo) -> Shape:
    import math
    n, w, m = d.tokens, algo.merge_window, 0
    if w:
        m = min(w, max(1, math.ceil(algo.merge_ratio * w)))
        n = n // w * m
    motion = max(1, int(round(algo.capacity * n))) if algo.fastcache else n
    return Shape(d, n, motion, w, m)


def request(s: Shape, fastcache: bool, rows: float, steps: int,
            computed: float, skipped: float) -> float:
    """FLOPs that one request's first ``steps`` steps need, over its
    ``rows`` model rows (2 under guidance, 1 at a scale of 1, whose
    unconditional row is thrown away).  ``computed`` and ``skipped`` are
    the request's block counters over those rows; a first step counts
    every block on every token."""
    if steps == 0:
        return 0.0
    d = s.d
    D, F, L = d.hidden, d.mlp, d.depth
    per_step = embed_and_final(d)
    if s.window:
        per_step += merge(d.tokens, s.window, s.centres, D)
    total = rows * steps * per_step
    if not fastcache:
        return total + rows * steps * L * block(s.n, D, F)
    total += rows * L * block(s.n, D, F)                  # the first step
    gated = max(0.0, computed - rows * L)
    total += gated * block(s.motion, D, F)
    total += skipped * 2 * s.motion * D * D               # linear approx
    total += rows * (steps - 1) * 2 * (s.n - s.motion) * D * D  # bypass
    return total


class Cost(NamedTuple):
    flops: float
    bytes: float

    def seconds(self, peak) -> float:
        """The least time on a chip: bound by compute or by HBM."""
        return max(self.flops / peak.bf16_flops,
                   self.bytes / peak.hbm_bytes_s)


def knn_density(windows: int, w: int, D: int, item: int = 2) -> Cost:
    return Cost(2.0 * windows * w * w * D,
                windows * w * D * item + windows * w * 4)


def merge_assign(windows: int, w: int, m: int, D: int,
                 item: int = 2) -> Cost:
    return Cost(4.0 * windows * w * m * D,
                windows * w * D * item + windows * w * 4
                + windows * m * D * item + windows * w * 4 + windows * m * 4)


def unmerge_scatter(windows: int, w: int, m: int, D: int,
                    item: int = 2) -> Cost:
    return Cost(0.0, windows * m * D * item + windows * w * 4
                + windows * w * D * item)


def kernel_costs(s: Shape, rows: int) -> Dict[str, Cost]:
    """Per-call cost of the token-merge kernels at a cell's shapes
    (``rows`` model rows in the engine's batch).  The fused cache gate has
    none: XLA keeps its operands in the chip's on-core memory, so its
    bytes over HBM bandwidth bound nothing."""
    D = s.d.hidden
    out = {}
    if s.window:
        nw = rows * s.d.tokens // s.window
        out["knn_density"] = knn_density(nw, s.window, D)
        out["merge_assign"] = merge_assign(nw, s.window, s.centres, D)
        out["unmerge_scatter"] = unmerge_scatter(nw, s.window, s.centres, D)
    return out
