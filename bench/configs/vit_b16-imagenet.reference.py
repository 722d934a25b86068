"""Plain reference of ViT-B/16 training: forward, loss, gradients, SGD.

Straightforward ``jax.numpy``, written from the model's description and
not from the consumer: each matmul is an ``einsum`` at the precision it
is given (``"highest"`` for the reference), layer norm, softmax and GELU
(its tanh form, as the consumer's ``jax.nn.gelu`` computes it) spelled
out.  Patches flatten as (channel, row, column), tokens are the 196
patches with a learned position embedding, the head reads their mean.

``train(..., dtype=jnp.bfloat16)`` is the control: the same steps with
parameters, activations and updates held in bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _ln(v, gamma, eps):
    mu = jnp.mean(v, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(v - mu), axis=-1, keepdims=True)
    return (v - mu) / jnp.sqrt(var + eps) * gamma


def _gelu(v):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * v * (1.0 + jnp.tanh(c * (v + 0.044715 * v * v * v)))


def _softmax(s):
    e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    return e / jnp.sum(e, axis=-1, keepdims=True)


def logits(model: dict, params, x, precision, dtype):
    p, d, heads = model["patch_size"], model["hidden_size"], model["num_attention_heads"]
    hd = d // heads
    b, c, h, w = x.shape
    ein = lambda spec, *args: jnp.einsum(spec, *args, precision=precision)
    x = x.astype(dtype).reshape(b, c, h // p, p, w // p, p)
    proj = params["proj"].reshape(c, p, p, d)
    t = ein("bcyixj,cijd->byxd", x, proj).reshape(b, (h // p) * (w // p), d)
    t = t + params["pos"]
    for blk in params["blocks"]:
        u = _ln(t, blk["ln1"], model["layer_norm_eps"])
        qkv = ein("btd,de->bte", u, blk["qkv"]).reshape(b, -1, 3, heads, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        a = _softmax(ein("bqhe,bkhe->bhqk", q, k) / math.sqrt(hd))
        o = ein("bhqk,bkhe->bqhe", a, v).reshape(b, -1, d)
        t = t + ein("btd,de->bte", o, blk["attn_o"])
        u = _ln(t, blk["ln2"], model["layer_norm_eps"])
        m = _gelu(ein("btd,df->btf", u, blk["mlp_up"]))
        t = t + ein("btf,fd->btd", m, blk["mlp_dn"])
    return ein("bd,dk->bk", jnp.mean(t, axis=1), params["head"])


def loss(model: dict, params, x, labels, precision, dtype):
    z = logits(model, params, x, precision, dtype).astype(jnp.float32)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    logp = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def _norms(tree):
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for leaf in jax.tree_util.tree_leaves(tree)
    ])


def train(model: dict, params, batches, labels, lr: float, *, precision="highest",
          dtype=jnp.float32) -> dict:
    """``len(batches)`` SGD steps from ``params``.  Returns each step's loss
    (before its update), each leaf's norm of the first gradient, directly
    (``grad_norms``) and as the update applied it (``applied_grad_norms``,
    ``(p0 - p1) / lr`` in the parameters' own type), and each leaf's norm
    of the change over all the steps (``update_norms``)."""

    @jax.jit
    def step(ps, x, y):
        value, g = jax.value_and_grad(lambda q: loss(model, q, x, y, precision, dtype))(ps)
        new = jax.tree_util.tree_map(lambda a, b: (a - lr * b).astype(dtype), ps, g)
        return new, value, _norms(g)

    diff_norms = jax.jit(lambda a, b: _norms(jax.tree_util.tree_map(
        lambda u, v: u.astype(jnp.float32) - v.astype(jnp.float32), a, b)))
    p0 = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    ps, losses, out = p0, [], {}
    for k, x in enumerate(batches):
        new, value, gnorm = step(ps, x, labels)
        if k == 0:
            out["grad_norms"] = np.asarray(gnorm)
            out["applied_grad_norms"] = np.asarray(diff_norms(ps, new)) / lr
        ps = new
        losses.append(float(value))
    out["update_norms"] = np.asarray(diff_norms(ps, p0))
    out["losses"] = losses
    return out
