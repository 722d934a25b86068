"""Operations and bytes of the work, counted from shapes."""

from __future__ import annotations


def vit_forward_flop(model: dict) -> int:
    """Forward FLOPs of one image through ViT (patch tokens only, no class
    token, mean-pooled head): 2 per multiply-add of every matmul."""
    p, d, mlp = model["patch_size"], model["hidden_size"], model["intermediate_size"]
    h, w = model["image_size"]
    t = (h // p) * (w // p)
    patch = 2 * t * (3 * p * p) * d
    block = (
        2 * t * d * 3 * d  # qkv
        + 2 * t * t * d  # scores
        + 2 * t * t * d  # weighted values
        + 2 * t * d * d  # attention output
        + 2 * 2 * t * d * mlp  # mlp up and down
    )
    head = 2 * d * model["num_classes"]
    return patch + model["num_hidden_layers"] * block + head


def vit_train_flop(model: dict) -> int:
    """Forward and backward: the backward pass costs twice the forward."""
    return 3 * vit_forward_flop(model)


def decode_least_bytes(out_hw, channels: int = 3) -> int:
    """Bytes the on-chip decode must move per image, whatever implements it:
    the uint8 crop window in and the bfloat16 output out."""
    oh, ow = out_hw
    return oh * ow * channels * (1 + 2)
