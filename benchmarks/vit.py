"""The image cell's consumer: a ViT training step on the loader's batches.

Self-contained (params are a plain pytree, plain SGD) so it runs without
the model stack.  It eats the device-decode output layout, bf16 NCHW, and
patchifies with true /patch tokens.  Widths come from one ``ViTConfig``:
``VIT_B16`` holds the published ViT-B/16 widths (Dosovitskiy et al., 2021,
Table 1, "ViT-Base"; 1000 ImageNet classes); ``bench_e2e`` cuts them for
its CPU gates.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    patch: int = 16
    d_model: int = 768
    depth: int = 12
    heads: int = 12
    mlp: int = 3072
    n_classes: int = 1000


VIT_B16 = ViTConfig()


def init(cfg: ViTConfig, key, hw: tuple[int, int]):
    """Random params for ``hw`` images (seeded by ``key``)."""
    d, p = cfg.d_model, cfg.patch
    n_tok = (hw[0] // p) * (hw[1] // p)
    in_dim = 3 * p * p
    ks = iter(jax.random.split(key, 3 + 8 * cfg.depth))
    g = lambda shape, s: (jax.random.normal(next(ks), shape, jnp.float32) * s)
    return {
        "proj": g((in_dim, d), in_dim**-0.5),
        "pos": g((n_tok, d), 0.02),
        "head": g((d, cfg.n_classes), d**-0.5),
        "blocks": [
            {
                "ln1": jnp.ones((d,)),
                "ln2": jnp.ones((d,)),
                "qkv": g((d, 3 * d), d**-0.5),
                "attn_o": g((d, d), d**-0.5),
                "mlp_up": g((d, cfg.mlp), d**-0.5),
                "mlp_dn": g((cfg.mlp, d), cfg.mlp**-0.5),
            }
            for _ in range(cfg.depth)
        ],
    }


def apply(cfg: ViTConfig, params, x):  # x: (B, 3, H, W), the device-decode layout
    b, c, h, w = x.shape
    p, d, heads = cfg.patch, cfg.d_model, cfg.heads
    nh, nw = h // p, w // p
    x = x.astype(jnp.float32)
    x = x.reshape(b, c, nh, p, nw, p)
    x = x.transpose(0, 2, 4, 1, 3, 5).reshape(b, nh * nw, c * p * p)
    hdn = x @ params["proj"] + params["pos"]

    def ln(v, gamma):
        mu = v.mean(-1, keepdims=True)
        var = ((v - mu) ** 2).mean(-1, keepdims=True)
        return (v - mu) * jax.lax.rsqrt(var + 1e-6) * gamma

    for blk in params["blocks"]:
        y = ln(hdn, blk["ln1"])
        qkv = (y @ blk["qkv"]).reshape(b, -1, 3, heads, d // heads)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d // heads) ** -0.5
        a = jax.nn.softmax(s, axis=-1)
        y = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(b, -1, d)
        hdn = hdn + y @ blk["attn_o"]
        y = ln(hdn, blk["ln2"])
        hdn = hdn + jax.nn.gelu(y @ blk["mlp_up"]) @ blk["mlp_dn"]
    return hdn.mean(1) @ params["head"]


def loss(cfg: ViTConfig, params, x, labels):
    """Mean cross-entropy of ``labels`` (B,) int32 under the model."""
    logp = jax.nn.log_softmax(apply(cfg, params, x))
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()


def make_step(cfg: ViTConfig):
    """Jitted ``step(params, x, labels) -> (params, loss)``: one SGD step;
    ``loss`` is taken before the update."""

    @jax.jit
    def step(params, x, labels):
        value, grads = jax.value_and_grad(lambda p: loss(cfg, p, x, labels))(params)
        params = jax.tree_util.tree_map(lambda a, g: a - 1e-3 * g, params, grads)
        return params, value

    return step
