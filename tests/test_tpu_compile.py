"""Compile-only checks against a described TPU v5e topology: the Pallas
kernels of the serving path, compiled (``interpret=False``) at DiT-XL/2
widths with bf16 inputs as the serve step feeds them, and one jitted
fastcache ``CachedDiT`` step.

Nothing runs: the TPU compiler is handed shapes for a chip that is described,
not attached, and refuses what the chip would refuse (block shapes off the
(8, 128) tiling, fast-memory overruns, unpartitionable kernels).  The
topology is described inside a module fixture — never at import — because
only one process may hold the TPU library, and a test worker that collects
this file must not take it unless it runs these tests.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import FastCacheConfig
from repro.core import CachedDiT
from repro.kernels import fused_gate as fg
from repro.kernels import knn_density as kd
from repro.kernels import ops as kernel_ops
from repro.kernels import token_merge as tm
from repro.models import build_model

BF16, F32 = jnp.bfloat16, jnp.float32
# DiT-XL/2 serving widths: 4 slots x CFG pair = 8 state rows, 256 tokens
# (32x32x4 latents, patch 2), motion capacity 0.5 -> C = 128, d = 1152;
# token-merge windows of 16 tokens at ratio 0.5 -> 8 centers per window
ROWS, TOKENS, C, D, WIN, CENTERS = 8, 256, 128, 1152, 16, 8
N_WIN = ROWS * TOKENS // WIN


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # the TPU compiler otherwise writes its logs outside the checkout
    log_dir_was = os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler plug-in in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile cannot be read back from the persistent
    # cache without a chip, so keep it out of any configured cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    if log_dir_was == "disabled":
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_fused_gate_compiles(one_chip):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    txt = _compiled_text(
        lambda *a: fg.fused_gate(*a, threshold=1.05, gamma=0.5,
                                 use_blend=True, interpret=False),
        s((ROWS, C, D), BF16), s((ROWS, C, D), BF16), s((ROWS, C, D), BF16),
        s((D, D), F32), s((D,), F32), s((ROWS,), F32), s((ROWS,), jnp.bool_))
    assert "tpu_custom_call" in txt


def test_knn_density_compiles(one_chip):
    h = jax.ShapeDtypeStruct((N_WIN, WIN, D), BF16, sharding=one_chip)
    txt = _compiled_text(lambda h: kd.knn_density(h, k=5, interpret=False), h)
    assert "tpu_custom_call" in txt


def test_merge_assign_compiles(one_chip):
    h = jax.ShapeDtypeStruct((N_WIN, WIN, D), BF16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((N_WIN, WIN), F32, sharding=one_chip)
    txt = _compiled_text(
        lambda h, s: tm.merge_assign(h, s, m=CENTERS, interpret=False), h, s)
    assert "tpu_custom_call" in txt


def test_unmerge_scatter_compiles(one_chip):
    merged = jax.ShapeDtypeStruct((N_WIN, CENTERS, D), BF16,
                                  sharding=one_chip)
    assign = jax.ShapeDtypeStruct((N_WIN, WIN), jnp.int32, sharding=one_chip)
    txt = _compiled_text(
        lambda m, a: tm.unmerge_scatter(m, a, interpret=False), merged,
        assign)
    assert "tpu_custom_call" in txt


def test_fastcache_step_compiles_at_xl(one_chip, monkeypatch):
    """One jitted fastcache ``CachedDiT`` step at DiT-XL/2 width with the
    fused gate: the kernel inside the 28-layer scan, under the three-way
    cold / mixed / gated dispatch."""
    # the kernel wrappers pick the interpreter off the chip; the compile is
    # for the chip, so they must pick the compiled kernel here
    monkeypatch.setattr(kernel_ops, "_auto_interpret", lambda: False)
    cfg = get_config("dit-xl2")
    model = build_model(cfg)
    runner = CachedDiT(model, FastCacheConfig(use_fused_gate=True),
                       policy="fastcache")

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    dit = cfg.dit
    args = (on_chip(model.abstract_params()),
            on_chip(jax.eval_shape(lambda: runner.init_state(ROWS))),
            on_chip(jax.ShapeDtypeStruct(
                (ROWS, dit.image_size, dit.image_size, dit.in_channels),
                F32)),
            on_chip(jax.ShapeDtypeStruct((ROWS,), jnp.int32)),
            on_chip(jax.ShapeDtypeStruct((ROWS,), jnp.int32)))
    txt = _compiled_text(runner.step, *args)
    assert "tpu_custom_call" in txt


def test_fastcache_gated_step_updates_payload_in_place(one_chip,
                                                      monkeypatch):
    """The gated fastcache step (every step of a request after its first)
    at the 512x512 serving widths: 8 rows, 1024 latent tokens merged to
    512, motion capacity C 256, d 1152, 28 blocks.  Compiled with the state
    donated, as the engine does, the payload stack ``prev_hidden`` of the
    output takes the donated input's buffer, and the step needs less
    scratch memory than one (L, B, N, D) stack: it touches the stack only
    at the motion tokens, and copies none of it."""
    monkeypatch.setattr(kernel_ops, "_auto_interpret", lambda: False)
    cfg = get_config("dit-xl2")
    cfg = cfg.replace(dit=dataclasses.replace(cfg.dit, image_size=64))
    model = build_model(cfg)
    runner = CachedDiT(model, FastCacheConfig(use_fused_gate=True,
                                              merge_enabled=True,
                                              merge_ratio=0.5),
                       policy="fastcache")
    policy = runner.impl
    assert (policy.n_tokens, policy.capacity) == (512, 256)
    monkeypatch.setattr(policy, "step", policy._gated_step)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    dit = cfg.dit
    state = jax.eval_shape(lambda: runner.init_state(ROWS))
    stack = state["prev_hidden"]
    assert stack.shape == (cfg.num_layers + 1, ROWS, 512, D)
    compiled = jax.jit(runner.step, donate_argnums=(1,)).lower(
        on_chip(model.abstract_params()), on_chip(state),
        on_chip(jax.ShapeDtypeStruct(
            (ROWS, dit.image_size, dit.image_size, dit.in_channels), F32)),
        on_chip(jax.ShapeDtypeStruct((ROWS,), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((ROWS,), jnp.int32))).compile()
    txt = compiled.as_text()
    dims = ",".join(map(str, stack.shape))
    entry = txt[txt.index("\nENTRY "):]
    param = re.search(rf"= bf16\[{dims}\]\S* parameter\((\d+)\)",
                      entry).group(1)
    # an input aliases only an output of its own shape, and prev_hidden is
    # the step's one output of the stack's shape
    assert re.search(rf"\{{\d+\}}: \({param}, \{{\}}, may-alias\)", txt)
    layer_stack = (stack.size // stack.shape[0] * cfg.num_layers
                   * stack.dtype.itemsize)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_stack


def test_serving_kernels_compile_per_shard_on_a_mesh(topo, monkeypatch):
    """The compiler refuses to partition a Mosaic kernel ("Mosaic kernels
    cannot be automatically partitioned"); under a (2, 2) serving mesh the
    fused gate and the merge kernel run per shard of their leading axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed.sharding import make_rules, use_sharding
    from repro.launch.mesh import make_mesh
    monkeypatch.setattr(kernel_ops, "_auto_interpret", lambda: False)
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    row, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def s(shape, dt, sh=row):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    def serve(x, prev_in, prev_out, w, b, sigma2, eligible, h, scores):
        with use_sharding(mesh, make_rules("serve")):
            gated = kernel_ops.fused_gate(x, prev_in, prev_out, w, b, sigma2,
                                          eligible, threshold=1.05)
            return gated, kernel_ops.merge_assign(h, scores, m=CENTERS)

    txt = _compiled_text(
        serve, s((ROWS, C, D), BF16), s((ROWS, C, D), BF16),
        s((ROWS, C, D), BF16), s((D, D), F32, rep), s((D,), F32, rep),
        s((ROWS,), F32), s((ROWS,), jnp.bool_), s((N_WIN, WIN, D), BF16),
        s((N_WIN, WIN), F32))
    assert txt.count("tpu_custom_call") >= 2


def test_serving_kernels_are_named_for_the_benchmark(one_chip, monkeypatch):
    """Each serving-path kernel's custom call in a compiled fastcache step
    with token merging carries the name the benchmark's trace reduction
    looks for (``bench/trace_reduce.KERNELS``, matched on the HLO
    instruction name without its ``.<n>`` suffix): a renamed kernel fails
    here instead of silencing ``merge_roofline``."""
    from bench.trace_reduce import KERNELS
    monkeypatch.setattr(kernel_ops, "_auto_interpret", lambda: False)
    cfg = get_config("dit-xl2").replace(num_layers=2)
    model = build_model(cfg)
    runner = CachedDiT(model, FastCacheConfig(use_fused_gate=True,
                                              merge_enabled=True,
                                              merge_ratio=0.5),
                       policy="fastcache")

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    dit = cfg.dit
    txt = _compiled_text(
        runner.step, on_chip(model.abstract_params()),
        on_chip(jax.eval_shape(lambda: runner.init_state(ROWS))),
        on_chip(jax.ShapeDtypeStruct(
            (ROWS, dit.image_size, dit.image_size, dit.in_channels), F32)),
        on_chip(jax.ShapeDtypeStruct((ROWS,), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((ROWS,), jnp.int32)))
    names = {m.group(1) for m in re.finditer(
        r"%([A-Za-z_][A-Za-z0-9_\-]*)(?:\.\d+)* = [^\n]*custom-call\([^\n]*"
        r'custom_call_target="tpu_custom_call"', txt)}
    assert names == set(KERNELS)
