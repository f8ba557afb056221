"""DiT (Peebles & Xie 2023): patchified latent tokens, adaLN-zero blocks.

This is the paper's backbone. ``block_apply`` exposes single-block execution
so the FastCache runner (repro.core.runner) can gate each block with the
statistical cache test and substitute the learnable linear approximation.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import common, flags
from repro.models.attention import attention
from repro.models.params import ParamDef, abstract_params, init_params

F32 = jnp.float32


def _ln(x):
    """LayerNorm without affine params (DiT uses modulate instead)."""
    xf = x.astype(F32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + 1e-6)).astype(x.dtype)


def unzero_params(params, key: jax.Array, *,
                  rescale_attention: bool = False):
    """Seeded, non-degenerate stand-ins for trained DiT weights.

    adaLN-zero init makes every untrained block the identity (its gates
    are 0) and the zero-init output head makes eps == 0 identically, so
    with ``DiTModel.init`` weights every cache policy is trivially exact
    and every comparison trivially passes.  This replaces the block
    modulation and the output head with seeded normals, so blocks
    transform like a trained model's would.  Leaves keep their dtypes.

    ``rescale_attention`` also brings the head-split attention projections
    to the scale of their true fan-in (d_model for ``wq``/``wk``/``wv``,
    heads x head_dim for ``wo``).  The ``fan_in`` initializer reads the
    fan-in off ``shape[-2]``, which for these projections is the head count
    (``wq``) or the head width (``wo``): at DiT-XL/2 width the q/k logits
    come out ~8x too large and the random network is chaotic — a 1e-3
    input perturbation moves the output by 4e-2 after four blocks, and bf16
    rounding alone moves it by O(1).  Rescaled, bf16 stays within ~1e-2 of
    a float32 forward.  Off by default: the benchmarks' recorded
    trajectories were made with the unscaled weights."""
    blocks = dict(params["blocks"])
    d = params["final_w"].shape[0]
    ada_w = 0.05 * jax.random.normal(key, blocks["ada_w"].shape)
    ada_b = 0.2 * jax.random.normal(jax.random.fold_in(key, 1),
                                    blocks["ada_b"].shape)
    final_w = (jax.random.normal(jax.random.fold_in(key, 2),
                                 params["final_w"].shape) / d ** 0.5)
    blocks["ada_w"] = ada_w.astype(blocks["ada_w"].dtype)
    blocks["ada_b"] = ada_b.astype(blocks["ada_b"].dtype)
    if rescale_attention:
        heads = blocks["wq"].shape[-2]                     # (L, d, h, dh)
        for name, factor in (("wq", heads / d), ("wk", heads / d),
                             ("wv", heads / d), ("wo", 1.0 / heads)):
            w = blocks[name]
            blocks[name] = (w.astype(jnp.float32)
                            * factor ** 0.5).astype(w.dtype)
    return {**params, "blocks": blocks,
            "final_w": final_w.astype(params["final_w"].dtype)}


class DiTModel:
    def __init__(self, cfg: ModelConfig):
        if cfg.family != "dit" or cfg.dit is None:
            raise ValueError(f"DiTModel requires a dit-family config with "
                             f"cfg.dit set; got family={cfg.family!r}")
        self.cfg = cfg
        dit = cfg.dit
        self.grid = dit.image_size // dit.patch_size
        self.num_tokens = self.grid * self.grid
        self.patch_dim = dit.patch_size ** 2 * dit.in_channels
        self.out_dim = self.patch_dim * (2 if dit.learn_sigma else 1)

    # ------------------------------------------------------------------

    def _block_defs(self) -> Dict[str, ParamDef]:
        cfg = self.cfg
        d, h = cfg.d_model, cfg.num_heads
        dh = cfg.resolved_head_dim
        f = cfg.d_ff
        return {
            "ada_w": ParamDef((d, 6 * d), ("embed", None), "zeros"),
            "ada_b": ParamDef((6 * d,), (None,), "zeros"),
            "wq": ParamDef((d, h, dh), ("embed", "heads", "head_dim"), "fan_in"),
            "wk": ParamDef((d, h, dh), ("embed", "heads", "head_dim"), "fan_in"),
            "wv": ParamDef((d, h, dh), ("embed", "heads", "head_dim"), "fan_in"),
            "wo": ParamDef((h, dh, d), ("heads", "head_dim", "embed"), "fan_in"),
            "w_in": ParamDef((d, f), ("embed", "ffn"), "fan_in"),
            "b_in": ParamDef((f,), ("ffn",), "zeros"),
            "w_out": ParamDef((f, d), ("ffn", "embed"), "fan_in"),
            "b_out": ParamDef((d,), ("embed",), "zeros"),
        }

    def param_defs(self):
        cfg = self.cfg
        d = cfg.d_model
        from repro.models.layers import stack_defs
        return {
            "patch_w": ParamDef((self.patch_dim, d), (None, "embed"), "fan_in"),
            "patch_b": ParamDef((d,), ("embed",), "zeros"),
            "pos_emb": ParamDef((self.num_tokens, d), (None, "embed"),
                                "normal"),
            "t_w1": ParamDef((256, d), (None, "embed"), "fan_in"),
            "t_b1": ParamDef((d,), ("embed",), "zeros"),
            "t_w2": ParamDef((d, d), ("embed", "embed"), "fan_in"),
            "t_b2": ParamDef((d,), ("embed",), "zeros"),
            "label_emb": ParamDef((cfg.dit.num_classes + 1, d),
                                  (None, "embed"), "normal"),
            "blocks": stack_defs(self._block_defs(), cfg.num_layers),
            "final_ada_w": ParamDef((d, 2 * d), ("embed", None), "zeros"),
            "final_ada_b": ParamDef((2 * d,), (None,), "zeros"),
            "final_w": ParamDef((d, self.out_dim), ("embed", None), "zeros"),
            "final_b": ParamDef((self.out_dim,), (None,), "zeros"),
        }

    def init(self, key):
        return init_params(self.param_defs(), key, self.cfg.dtype)

    def abstract_params(self):
        return abstract_params(self.param_defs(), self.cfg.dtype)

    # ------------------------------------------------------------------

    def conditioning(self, params, t: jax.Array, labels: jax.Array):
        """(B,) timesteps + (B,) labels -> (B, D) conditioning vector."""
        temb = common.timestep_embedding(t, 256)
        temb = common.fdot(temb.astype(jnp.dtype(self.cfg.dtype)),
                           params["t_w1"]) + params["t_b1"]
        temb = jax.nn.silu(temb.astype(F32)).astype(temb.dtype)
        temb = common.fdot(temb, params["t_w2"]) + params["t_b2"]
        yemb = jnp.take(params["label_emb"], labels, axis=0)
        return temb + yemb

    def block_apply(self, bp, x: jax.Array, c: jax.Array) -> jax.Array:
        """One DiT block. x: (B,N,D); c: (B,D).

        Its parts carry the named scopes ``adaln`` (the modulation vector
        and both modulated norms), ``attention`` (q/k/v, attention, output
        projection, gated residual) and ``mlp`` (MLP and gated residual),
        so a device trace can put each op's time down to a part."""
        with jax.named_scope("adaln"):
            mod = common.fdot(jax.nn.silu(c.astype(F32)).astype(x.dtype),
                              bp["ada_w"]) + bp["ada_b"]
            sh1, sc1, g1, sh2, sc2, g2 = jnp.split(mod, 6, axis=-1)
            h = common.modulate(_ln(x), sh1, sc1)
        with jax.named_scope("attention"):
            q = common.feinsum("bnd,dhk->bnhk", h, bp["wq"])
            k = common.feinsum("bnd,dhk->bnhk", h, bp["wk"])
            v = common.feinsum("bnd,dhk->bnhk", h, bp["wv"])
            pos = jnp.arange(x.shape[1])
            o = attention(q, k, v, pos, pos, causal=False)
            o = common.feinsum("bnhk,hkd->bnd", o, bp["wo"])
            x = x + g1[:, None, :] * o
        with jax.named_scope("adaln"):
            h = common.modulate(_ln(x), sh2, sc2)
        with jax.named_scope("mlp"):
            h = common.gelu_mlp(h, bp["w_in"], bp["b_in"], bp["w_out"],
                                bp["b_out"])
            x = x + g2[:, None, :] * h
        return constrain(x, "act_batch", "act_seq", "act_embed")

    def final_layer(self, params, x: jax.Array, c: jax.Array) -> jax.Array:
        mod = common.fdot(jax.nn.silu(c.astype(F32)).astype(x.dtype),
                          params["final_ada_w"]) + params["final_ada_b"]
        sh, sc = jnp.split(mod, 2, axis=-1)
        x = common.modulate(_ln(x), sh, sc)
        return common.fdot(x, params["final_w"]) + params["final_b"]

    # ------------------------------------------------------------------

    def tokens_in(self, params, latents: jax.Array) -> jax.Array:
        """(B, Hs, Ws, C) -> (B, N, D) with positional embedding."""
        p = self.cfg.dit.patch_size
        tok = common.patchify(latents.astype(jnp.dtype(self.cfg.dtype)), p)
        x = common.fdot(tok, params["patch_w"]) + params["patch_b"]
        return x + params["pos_emb"][None]

    def apply(self, params, batch, train: bool = False):
        """batch: latents (B,Hs,Ws,C), t (B,), labels (B,). -> (eps, aux)."""
        cfg = self.cfg
        x = self.tokens_in(params, batch["latents"])
        c = self.conditioning(params, batch["t"], batch["labels"])

        def scan_body(x, bp):
            return self.block_apply(bp, x, c), None

        body = scan_body
        if train and cfg.remat:
            body = jax.checkpoint(scan_body)
        x, _ = jax.lax.scan(body, x, params["blocks"],
                            unroll=flags.scan_unroll(cfg.num_layers))
        out = self.final_layer(params, x, c)
        eps = common.unpatchify(out[..., :self.patch_dim] if
                                cfg.dit.learn_sigma else out,
                                cfg.dit.patch_size, self.grid)
        return eps, {"moe_aux": jnp.zeros((), F32)}

    def loss(self, params, batch) -> Tuple[jax.Array, dict]:
        """Denoising MSE: predict the noise added to clean latents."""
        eps_hat, _ = self.apply(params, batch, train=True)
        mse = jnp.mean(jnp.square(eps_hat.astype(F32)
                                  - batch["noise"].astype(F32)))
        return mse, {"mse": mse}
