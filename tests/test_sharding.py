"""Sharding rules: divisibility fallback, axis-conflict handling, per-shape
rule tables, optimizer-state sharding trees."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (ShardingCtx, make_rules, spec_for,
                                        param_shardings, use_sharding)
from repro.launch.mesh import make_host_mesh
from repro.models.params import ParamDef


@pytest.fixture(scope="module")
def ctx():
    mesh = make_host_mesh()
    return ShardingCtx(mesh, make_rules("train"))


def test_spec_basic(ctx):
    assert spec_for((64, 32), ("embed", "ffn"), ctx) == P("data", "model")


def test_divisibility_fallback(ctx):
    # 1-device axes divide everything; build a fake larger mesh via rules on
    # a mesh with extent 1 is trivial — exercise the arithmetic directly
    mesh = make_host_mesh()
    big = ShardingCtx(mesh, make_rules("train"))
    assert spec_for((504,), ("vocab",), big) in (P("model"), P(None))


def test_axis_conflict_drops_second_use():
    mesh = make_host_mesh()
    rules = make_rules("train")
    rules["a"] = ("model",)
    rules["b"] = ("model",)
    ctx = ShardingCtx(mesh, rules)
    spec = spec_for((8, 8), ("a", "b"), ctx)
    assert spec[1] is None  # model already consumed by dim 0


def test_long_context_rules_move_data_axis():
    r = make_rules("decode", long_context=True)
    assert "data" in r["act_kv_seq"]
    assert r["act_batch"] == ("pod",)


def test_decode_rules_shard_kv_over_model():
    r = make_rules("decode")
    assert r["act_kv_seq"] == ("model",)


def test_param_shardings_tree(ctx):
    defs = {"w": ParamDef((8, 4), ("embed", "ffn")),
            "nested": {"b": ParamDef((4,), ("ffn",))}}
    sh = param_shardings(defs, ctx)
    assert sh["w"].spec == P("data", "model")
    assert sh["nested"]["b"].spec == P("model")


def test_constrain_noop_outside_ctx():
    from repro.distributed.sharding import constrain
    x = jnp.ones((4, 4))
    assert constrain(x, "act_batch", None) is x


def test_constrain_applies_in_ctx():
    from repro.distributed.sharding import constrain
    mesh = make_host_mesh()
    with use_sharding(mesh, make_rules("train")):
        y = constrain(jnp.ones((4, 4)), "act_batch", "act_embed")
        assert y.shape == (4, 4)


def test_optimizer_shardings_match_structure():
    from repro.launch.specs import optimizer_shardings
    from repro.training.optimizer import Adafactor, AdamW
    mesh = make_host_mesh()
    ctx = ShardingCtx(mesh, make_rules("train"))
    defs = {"w": ParamDef((8, 4), ("embed", "ffn")),
            "b": ParamDef((4,), ("ffn",))}
    import jax as _jax
    from repro.models.params import abstract_params
    params = abstract_params(defs, "float32")
    for opt in (AdamW(), Adafactor()):
        sh = optimizer_shardings(opt, defs, ctx)
        sds = _jax.eval_shape(opt.init, params)
        # structures must line up leaf-for-leaf
        _jax.tree.map(lambda a, b: None, sds, sh)
