"""bench/flops.py against counts made by hand at DiT-XL/2 shapes."""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, reference, weights  # noqa: E402

XL = {"depth": 28, "hidden_size": 1152, "num_heads": 16, "patch_size": 2,
      "input_size": 32, "in_channels": 4, "mlp_ratio": 4.0,
      "num_classes": 1000, "learn_sigma": True, "policy": "fastcache",
      "fastcache": {}}
D, F, L = 1152, 4608, 28


def block_params():
    """Parameter counts of one block, read off the weight pytree's shapes."""
    d = weights.dims_of(XL)
    shapes = jax.eval_shape(lambda k: weights._make(k, d, jax.numpy.bfloat16),
                            jax.random.PRNGKey(0))["blocks"]
    count = {k: 1 for k in shapes}
    for k, s in shapes.items():
        for n in s.shape[1:]:
            count[k] *= n
    return count


def test_block_at_256_tokens():
    c = block_params()
    matmul = c["wq"] + c["wk"] + c["wv"] + c["wo"] + c["w_in"] + c["w_out"]
    assert matmul == 4 * D * D + 2 * D * F == 15_925_248
    # every token through every projection, attention over 256 keys for
    # scores and values, the adaLN modulation once per row
    by_hand = 2 * 256 * matmul + 2 * 2 * 256 * 256 * D + 2 * c["ada_w"]
    assert flops.block(256, D, F) == by_hand == 8_471_642_112


def test_attention_share_grows_with_tokens():
    def attn_share(n):
        return 4 * n * n * D / flops.block(n, D, F)
    assert attn_share(256) == pytest.approx(0.0356, abs=1e-3)
    assert attn_share(1024) == pytest.approx(0.1287, abs=1e-3)


def test_request_counts_from_counters():
    d = weights.dims_of(XL)
    s = flops.shape_of(d, reference.algo_of(XL))
    assert (s.n, s.motion, s.window) == (256, 128, 0)
    per_step = flops.embed_and_final(d)
    assert per_step == (2 * 256 * 16 * D + 2 * 256 * D + 2 * D * D
                        + 4 * D * D + 2 * 256 * D * 32)
    # 50 steps under guidance: the first step every block on 256 tokens,
    # then 2 x 49 x 28 gated (row, block) decisions of which 30% skipped
    skipped = 0.3 * 2 * 49 * L
    computed = 2 * L + 0.7 * 2 * 49 * L
    got = flops.request(s, True, rows=2, steps=50, computed=computed,
                        skipped=skipped)
    by_hand = (2 * 50 * per_step
               + 2 * L * flops.block(256, D, F)
               + 0.7 * 2 * 49 * L * flops.block(128, D, F)
               + skipped * 2 * 128 * D * D
               + 2 * 49 * 2 * 128 * D * D)
    assert got == pytest.approx(by_hand, rel=1e-12)
    # a cached path that skips nothing needs less than the dense forward
    dense = flops.request(s, False, rows=2, steps=50, computed=0, skipped=0)
    assert dense == pytest.approx(2 * 50 * (per_step
                                            + L * flops.block(256, D, F)))
    assert got < dense


def test_kernel_bytes_at_serving_shapes():
    cfg = dict(XL, input_size=64, fastcache={"merge_enabled": True,
                                            "merge_ratio": 0.5,
                                            "merge_window": 16})
    d = weights.dims_of(cfg)
    s = flops.shape_of(d, reference.algo_of(cfg))
    assert (s.n, s.motion, s.centres) == (512, 256, 8)
    k = flops.kernel_costs(s, rows=8)
    assert "fused_gate" not in k
    nw = 8 * 1024 // 16
    assert k["knn_density"] == (2 * nw * 16 * 16 * D,
                                nw * 16 * D * 2 + nw * 16 * 4)
    assert k["unmerge_scatter"].bytes == (nw * 8 * D * 2 + nw * 16 * 4
                                          + nw * 16 * D * 2)
    from bench.peaks import peak_of
    v5e = peak_of("TPU v5 lite")
    # every merge kernel is bound by HBM at these shapes
    for name in ("knn_density", "merge_assign", "unmerge_scatter"):
        c = k[name]
        assert c.seconds(v5e) == c.bytes / v5e.hbm_bytes_s
    with pytest.raises(KeyError):
        peak_of("cpu")
