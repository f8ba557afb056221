"""The paper's own backbones: DiT-S/2, DiT-B/2, DiT-L/2, DiT-XL/2
(Peebles & Xie 2023; FastCache paper Table 4).

| Model    | Layers | Hidden | Heads | Params (M) |
| DiT-S/2  |   6*   |  384   |   6   |  17        |  (*paper Table 4 lists 6)
| DiT-B/2  |  12    |  768   |  12   | 130        |
| DiT-L/2  |  24    | 1024   |  16   | 458        |
| DiT-XL/2 |  28    | 1152   |  18   | 675        |

Params (M) is the ``param_defs`` count of these configs (256x256 images as
32x32x4 latents, 1000 classes), rounded; tests/test_models.py pins it.

DiT blocks: full bidirectional attention over latent patch tokens, adaLN-zero
conditioning on (timestep, class), MLP ratio 4. vocab_size is unused (no token
embedding; patchified VAE latents in, noise prediction out).
"""
from repro.configs.base import DiTConfig, ModelConfig


def _dit(name: str, layers: int, d: int, heads: int) -> ModelConfig:
    return ModelConfig(
        name=name,
        family="dit",
        num_layers=layers,
        d_model=d,
        num_heads=heads,
        num_kv_heads=heads,
        d_ff=4 * d,
        vocab_size=0,
        rope_kind="none",
        is_encoder=True,
        dit=DiTConfig(patch_size=2, in_channels=4, num_classes=1000,
                      image_size=32),
    )


DIT_S2 = _dit("dit-s2", 6, 384, 6)
DIT_B2 = _dit("dit-b2", 12, 768, 12)
DIT_L2 = _dit("dit-l2", 24, 1024, 16)
DIT_XL2 = _dit("dit-xl2", 28, 1152, 18)

CONFIG = DIT_XL2


def reduced(name: str = "dit-smoke") -> ModelConfig:
    return _dit(name, 2, 128, 4).replace(
        dit=DiTConfig(patch_size=2, in_channels=4, num_classes=10, image_size=8))
