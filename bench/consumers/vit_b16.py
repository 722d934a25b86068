"""ViT training on the loader's batches: the benchmark's own consumer.

A copy of the repository's ViT step, kept here so that no change to the
program can change the chip's work per sample: float32 parameters, the
default matmul precision, plain SGD, patch tokens only, mean-pooled head.
It eats the on-chip decode's output, bfloat16 NCHW.  The jitted step is
named ``vit_b16_train_step``, so a trace shows it as
``jit_vit_b16_train_step``.

Set-up builds the compiled step and its parameters once, drives them
through the first ``setup_steps`` batches of the stream with the window's
own call, and hands the same objects to the window.  On the way it keeps
what the check compares: the loss of each of those steps, each leaf's norm
of the first gradient as SGD applied it, ``(p0 - p1) / lr``, and each
leaf's norm of the parameters' change over the steps, ``p3 - p0``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

STEP_NAME = "vit_b16_train_step"


def init(model: dict, key):
    """Random float32 parameters from ``key``."""
    d, p = model["hidden_size"], model["patch_size"]
    h, w = model["image_size"]
    n_tok = (h // p) * (w // p)
    in_dim = 3 * p * p
    mlp, depth = model["intermediate_size"], model["num_hidden_layers"]
    ks = iter(jax.random.split(key, 3 + 8 * depth))
    g = lambda shape, s: jax.random.normal(next(ks), shape, jnp.float32) * s
    return {
        "proj": g((in_dim, d), in_dim**-0.5),
        "pos": g((n_tok, d), 0.02),
        "head": g((d, model["num_classes"]), d**-0.5),
        "blocks": [
            {
                "ln1": jnp.ones((d,)),
                "ln2": jnp.ones((d,)),
                "qkv": g((d, 3 * d), d**-0.5),
                "attn_o": g((d, d), d**-0.5),
                "mlp_up": g((d, mlp), d**-0.5),
                "mlp_dn": g((mlp, d), mlp**-0.5),
            }
            for _ in range(depth)
        ],
    }


def apply(model: dict, params, x):  # x: (B, 3, H, W), the on-chip decode's layout
    b, c, h, w = x.shape
    p, d, heads = model["patch_size"], model["hidden_size"], model["num_attention_heads"]
    eps = model["layer_norm_eps"]
    nh, nw = h // p, w // p
    x = x.astype(jnp.float32)
    x = x.reshape(b, c, nh, p, nw, p)
    x = x.transpose(0, 2, 4, 1, 3, 5).reshape(b, nh * nw, c * p * p)
    hdn = x @ params["proj"] + params["pos"]

    def ln(v, gamma):
        mu = v.mean(-1, keepdims=True)
        var = ((v - mu) ** 2).mean(-1, keepdims=True)
        return (v - mu) * jax.lax.rsqrt(var + eps) * gamma

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


def loss(model: dict, params, x, labels):
    logp = jax.nn.log_softmax(apply(model, params, x))
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).mean()


def make_step(model: dict, lr: float):
    def vit_b16_train_step(params, x, labels):
        value, grads = jax.value_and_grad(lambda p: loss(model, p, x, labels))(params)
        params = jax.tree_util.tree_map(lambda a, g: a - lr * g, params, grads)
        return params, value

    return jax.jit(vit_b16_train_step)


@jax.jit
def leaf_norms(a, b):
    """Per-leaf ``||a - b||`` in float32, in ``tree_leaves`` order."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(x - y)))
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    ])


def labels_for(batch: int, n_classes: int) -> np.ndarray:
    return (np.arange(batch) % n_classes).astype(np.int32)


def param_key(seed: int):
    """A PRNG key from any non-negative seed (folded through SeedSequence,
    so seeds past 32 bits stay distinct)."""
    word = np.random.SeedSequence([seed, 4]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def make_params(model: dict, seed: int, replicated):
    """The parameters, made on the device in one jitted call."""
    return jax.jit(lambda k: init(model, k), out_shardings=replicated)(param_key(seed))


class Consumer:
    def __init__(self, config: dict, mesh, seed: int, batch: int):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.model = config["model"]
        self.lr = float(config["optimizer"]["learning_rate"])
        self.setup_steps = int(config["setup_steps"])
        self.batch = batch
        self.replicated = NamedSharding(mesh, P())
        self.params = make_params(self.model, seed, self.replicated)
        self.labels = jax.device_put(
            labels_for(batch, self.model["num_classes"]), NamedSharding(mesh, P("data"))
        )
        self.step = None
        self.losses: list[float] = []
        self.grad_norms = self.update_norms = None

    def compile(self, x) -> None:
        self.step = make_step(self.model, self.lr).lower(self.params, x, self.labels).compile()

    def dispatch(self, x):
        self.params, loss_value = self.step(self.params, x, self.labels)
        return loss_value

    def block(self, handle) -> None:
        handle.block_until_ready()

    def warm_up(self, next_batch) -> int:
        """The first ``setup_steps`` steps, through ``dispatch``; returns
        how many batches they took from the stream."""
        p0 = self.params
        pending = []
        for k in range(self.setup_steps):
            pending.append(self.dispatch(next_batch()))
            if k == 0:
                self.grad_norms = np.asarray(leaf_norms(p0, self.params)) / self.lr
        self.update_norms = np.asarray(leaf_norms(self.params, p0))
        self.losses = [float(v) for v in jax.device_get(pending)]
        return self.setup_steps

    def free(self) -> None:
        self.params = self.step = self.labels = None

    def check(self, ctx) -> tuple[dict, dict]:
        """The reference follows the first ``setup_steps`` steps from the
        same seed, on the same records, decoded by the reference; returns
        the numbers compared and, with ``ctx.calibrate``, the readings of
        the control and of the planted faults."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        ref_mod = ctx.reference_module()
        rows = NamedSharding(ctx.mesh, P("data"))
        p0 = make_params(self.model, ctx.seed, self.replicated)
        host = [ctx.reference_batch(k) for k in range(self.setup_steps)]
        y = labels_for(self.batch, self.model["num_classes"])

        def train(n_rows: int, **kw) -> dict:
            xs = [jax.device_put(x[:n_rows], rows) for x in host]
            return ref_mod.train(
                self.model, p0, xs, jax.device_put(y[:n_rows], rows), self.lr, **kw
            )

        ref = train(self.batch)
        prog = {"losses": self.losses, "grad_norms": self.grad_norms,
                "update_norms": self.update_norms}
        numbers = training_gaps(prog, ref)
        readings: dict = {}
        if ctx.calibrate:
            applied = lambda r: {"losses": r["losses"], "grad_norms": r["applied_grad_norms"],
                                 "update_norms": r["update_norms"]}
            readings["control"] = training_gaps(
                applied(train(self.batch, precision="default", dtype=jnp.bfloat16)), ref)
            readings["half_batch"] = training_gaps(
                applied(train(self.batch // 2, precision="default")), ref)
            chips = ctx.mesh.size
            if chips > 1:
                # without the exchange, chip 0 steps on the mean over its own rows
                readings["no_exchange"] = training_gaps(
                    applied(train(self.batch // chips, precision="default")), ref)
            zeros = np.zeros_like(ref["grad_norms"])
            readings["unchanged_state"] = training_gaps(
                {"losses": [ref["losses"][0]] * len(ref["losses"]), "grad_norms": zeros,
                 "update_norms": zeros}, ref)
        return numbers, readings


def training_gaps(prog: dict, ref: dict) -> dict:
    """Relative loss gap of the worst step, and the worst leaf's gap of
    norms, each leaf against the larger of its reference norm and the
    median leaf's.  Leaves whose reference gradient is under a thousandth
    of the median leaf's move by rounding alone and are left out."""
    g_ref = np.asarray(ref["grad_norms"], np.float64)
    keep = g_ref >= 1e-3 * np.median(g_ref)

    def worst(a, b) -> float:
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        den = np.maximum(b, np.median(b))
        return float(np.max(np.abs(a - b)[keep] / den[keep]))

    return {
        "loss_gap": max(
            abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])
        ),
        "grad_norm_gap": worst(prog["grad_norms"], ref["grad_norms"]),
        "update_norm_gap": worst(prog["update_norms"], ref["update_norms"]),
    }
