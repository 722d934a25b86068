"""Pallas TPU kernels: uint8 → bf16 dequantize + normalize (+ augment).

The device-side "last mile" of the data pipeline (DESIGN §6): the loader
transfers image batches as **uint8** (4× fewer PCIe/ICI bytes than f32,
2× fewer than bf16 — the paper's "avoid unnecessary memory copies"
principle extended to the wire), and these kernels expand to bf16 and
apply (x/255 − mean)/std on-chip, fused in one VMEM pass, emitting NCHW.

``dequant_normalize``          — dequant + per-channel normalize.
``dequant_normalize_augment``  — the full decode tail in ONE pass:
dynamic (top, left) crop to a static output window, per-sample horizontal
flip, dequant, per-channel normalize.  This is what ``DeviceTransfer``'s
``device_decode`` dispatches, so the host never touches a pixel float.

Grid: (batch, channels); each step moves one (H, W) plane HBM→VMEM,
crops via ``pl.ds`` dynamic slicing, applies flip + the affine transform
on the VPU, and writes the transposed layout.

TARGET: TPU; validated with ``interpret=True`` against the ``ref.py``
composition (``dequant_normalize_ref`` / ``dequant_normalize_augment_ref``).
Off the main path: the ``(1, H, W, 1)`` blocks over NHWC break the TPU
rule that the last two block dimensions be multiples of (8, 128) or whole,
so the TPU compiler refuses both kernels and ``ops`` dispatches the XLA
composition under ``use_pallas="auto"``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dequant_kernel(x_ref, mean_ref, std_ref, o_ref):
    # x_ref: (1, H, W, 1) uint8 ; mean/std: (1,) f32 ; o_ref: (1, 1, H, W)
    x = x_ref[0, :, :, 0].astype(jnp.float32) * (1.0 / 255.0)
    y = (x - mean_ref[0]) * (1.0 / std_ref[0])
    o_ref[0, 0] = y.astype(o_ref.dtype)


def dequant_normalize(
    x: jax.Array,  # (N, H, W, C) uint8
    mean: jax.Array,  # (C,) f32
    std: jax.Array,  # (C,) f32
    *,
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """Returns (N, C, H, W) ``out_dtype`` normalized images."""
    n, h, w, c = x.shape
    kernel = functools.partial(_dequant_kernel)
    return pl.pallas_call(
        kernel,
        grid=(n, c),
        in_specs=[
            pl.BlockSpec((1, h, w, 1), lambda ni, ci: (ni, 0, 0, ci)),
            pl.BlockSpec((1,), lambda ni, ci: (ci,)),
            pl.BlockSpec((1,), lambda ni, ci: (ci,)),
        ],
        out_specs=pl.BlockSpec((1, 1, h, w), lambda ni, ci: (ni, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c, h, w), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x, mean, std)


def _dequant_augment_kernel(
    x_ref, mean_ref, std_ref, flip_ref, crop_ref, o_ref, *, scale, out_h, out_w
):
    # x_ref: (1, H, W, 1) uint8/float ; mean/std: (1,) f32 ;
    # flip: (1,) i32 ; crop: (1, 2) i32 ; o_ref: (1, 1, out_h, out_w)
    oy = crop_ref[0, 0]
    ox = crop_ref[0, 1]
    # dynamic (top, left) crop straight out of the resident plane: one
    # VMEM slice, no gather
    y = x_ref[0, pl.ds(oy, out_h), pl.ds(ox, out_w), 0]
    y = y.astype(jnp.float32) * scale
    # both branches are computed on the VPU; select is elementwise
    y = jnp.where(flip_ref[0] != 0, y[:, ::-1], y)
    y = (y - mean_ref[0]) * (1.0 / std_ref[0])
    o_ref[0, 0] = y.astype(o_ref.dtype)


def dequant_normalize_augment(
    x: jax.Array,  # (N, H, W, C) uint8, or float already in [0, 1]
    mean: jax.Array,  # (C,) f32
    std: jax.Array,  # (C,) f32
    *,
    flip: jax.Array | None = None,  # (N,) nonzero = horizontal flip
    crop: jax.Array | None = None,  # (N, 2) (top, left) window offsets
    out_hw: tuple[int, int] | None = None,  # static window; None = full frame
    out_dtype=jnp.bfloat16,
    interpret: bool = False,
) -> jax.Array:
    """Fused decode tail: crop → flip → dequant → normalize → NCHW.

    Returns (N, C, out_h, out_w) ``out_dtype``.  Crop offsets are clamped
    in-bounds (``lax.dynamic_slice`` semantics, matching the ref).  Integer
    input is dequantized by 1/255; float input is assumed [0, 1] already.
    """
    n, h, w, c = x.shape
    oh, ow = out_hw if out_hw is not None else (h, w)
    if oh > h or ow > w:
        raise ValueError(f"out_hw={out_hw} exceeds input frame {(h, w)}")
    scale = (1.0 / 255.0) if jnp.issubdtype(x.dtype, jnp.integer) else 1.0
    if flip is None:
        flip = jnp.zeros((n,), jnp.int32)
    if crop is None:
        crop = jnp.zeros((n, 2), jnp.int32)
    crop = jnp.clip(
        crop.astype(jnp.int32), 0, jnp.array([h - oh, w - ow], jnp.int32)
    )
    kernel = functools.partial(
        _dequant_augment_kernel, scale=scale, out_h=oh, out_w=ow
    )
    return pl.pallas_call(
        kernel,
        grid=(n, c),
        in_specs=[
            pl.BlockSpec((1, h, w, 1), lambda ni, ci: (ni, 0, 0, ci)),
            pl.BlockSpec((1,), lambda ni, ci: (ci,)),
            pl.BlockSpec((1,), lambda ni, ci: (ci,)),
            pl.BlockSpec((1,), lambda ni, ci: (ni,)),
            pl.BlockSpec((1, 2), lambda ni, ci: (ni, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, oh, ow), lambda ni, ci: (ni, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c, oh, ow), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(x, mean, std, flip.astype(jnp.int32), crop)
