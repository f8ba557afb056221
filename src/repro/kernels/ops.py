"""Jitted public wrappers for the Pallas kernels.

``interpret`` defaults to auto: compiled Mosaic on TPU, the Pallas
interpreter elsewhere (CPU CI).  The interpreter executes the same kernel
bodies, so correctness tests on CPU transfer to TPU.

The compiler cannot partition a Mosaic kernel over a mesh.  The kernels of
the serving path (the fused gate and the three token-merge kernels) are
independent along their leading axis — one sample, or one window of one
sample — so under a multi-device sharding ctx (``use_sharding``) they run
per shard through ``shard_map``: leading-axis rows split over the mesh
axes that carry the activation batch, shared operands (the gate's linear
map) replicated.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_ctx, spec_for
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_gate as _fg
from repro.kernels import knn_density as _knn
from repro.kernels import linear_blend as _lb
from repro.kernels import saliency_delta as _sd
from repro.kernels import token_merge as _tm


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _rowwise(kernel, rows, shared=()):
    """``kernel(*rows, *shared)``; per shard of the leading axis under a
    multi-device sharding ctx (see the module docstring)."""
    ctx = current_ctx()
    if ctx is None or ctx.mesh.size == 1:
        return kernel(*rows, *shared)
    lead = P(spec_for((rows[0].shape[0],), ("act_batch",), ctx)[0])
    return jax.shard_map(
        kernel, mesh=ctx.mesh,
        in_specs=(lead,) * len(rows) + (P(),) * len(shared),
        out_specs=lead, check_vma=False)(*rows, *shared)


def default_use_fused() -> bool:
    """Backend auto-selection for the fused gate kernel: compiled Mosaic on
    TPU; elsewhere the pure-JAX reference path is both faster than the Pallas
    interpreter and the kernel's ground truth."""
    return jax.default_backend() == "tpu"


def saliency_delta(x, x_prev, *, bn: int = 128, bd: int = 512,
                   interpret=None):
    if interpret is None:
        interpret = _auto_interpret()
    return _sd.saliency_delta(x, x_prev, bn=bn, bd=bd, interpret=interpret)


def linear_blend(x, w, b, prev, *, gamma: float = 0.5, bm: int = 128,
                 bf: int = 256, bk: int = 256, interpret=None):
    if interpret is None:
        interpret = _auto_interpret()
    return _lb.linear_blend(x, w, b, prev, gamma=gamma, bm=bm, bf=bf, bk=bk,
                            interpret=interpret)


def fused_gate(x, prev_in, prev_out, w, b, sigma2, eligible, *,
               threshold: float, gamma: float = 0.5, use_blend: bool = True,
               bc: int = 0, interpret=None):
    if interpret is None:
        interpret = _auto_interpret()

    def kernel(x, prev_in, prev_out, sigma2, eligible, w, b):
        return _fg.fused_gate(x, prev_in, prev_out, w, b, sigma2, eligible,
                              threshold=threshold, gamma=gamma,
                              use_blend=use_blend, bc=bc,
                              interpret=interpret)
    return _rowwise(kernel, (x, prev_in, prev_out, sigma2, eligible), (w, b))


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, interpret=None):
    if interpret is None:
        interpret = _auto_interpret()
    return _fa.flash_attention(q, k, v, causal=causal, window=window, bq=bq,
                               bk=bk, interpret=interpret)


def knn_density(h, *, k: int = 5, interpret=None):
    if interpret is None:
        interpret = _auto_interpret()
    return _rowwise(functools.partial(_knn.knn_density, k=k,
                                      interpret=interpret), (h,))


def merge_assign(h, s, *, m: int, interpret=None):
    if interpret is None:
        interpret = _auto_interpret()
    return _rowwise(functools.partial(_tm.merge_assign, m=m,
                                      interpret=interpret), (h, s))


def unmerge_scatter(merged, assign, *, interpret=None):
    if interpret is None:
        interpret = _auto_interpret()
    return _rowwise(functools.partial(_tm.unmerge_scatter,
                                      interpret=interpret), (merged, assign))
