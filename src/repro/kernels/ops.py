"""Jit'd public wrappers for the Pallas kernels with backend dispatch.

``use_pallas`` selects the implementation:

* ``"auto"`` (default) — for ``flash_attention`` / ``ssd_scan`` the Pallas
  kernel on TPU and the jnp reference elsewhere.  The decode tail
  (``dequant_normalize`` / ``dequant_normalize_augment``) takes the XLA
  composition from ``ref.py`` on every backend: its Pallas kernels block
  ``(1, H, W, 1)`` over NHWC, which the TPU compiler refuses (the last two
  block dimensions must be multiples of (8, 128) or whole), so they stay
  off the main path.
* ``True`` — the Pallas kernel, compiled for the TPU.  Any other backend
  raises: it never silently falls back to interpret mode.
* ``"interpret"`` — the kernel body interpreted on any backend (the
  validation mode of the kernel test sweeps).
* ``False`` — the jnp reference.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import ref
from .dequant_normalize import dequant_normalize as _dequant_pallas
from .dequant_normalize import (
    dequant_normalize_augment as _dequant_augment_pallas,
)
from .flash_attention import flash_attention as _flash_pallas
from .ssd_scan import ssd_scan as _ssd_pallas


def _resolve(use_pallas: bool | str, auto: bool) -> tuple[bool, bool]:
    """→ (use_kernel, interpret); ``auto`` is what ``"auto"`` means here."""
    if use_pallas == "auto":
        return (auto, False)
    if use_pallas == "interpret":
        return (True, True)
    if use_pallas and jax.default_backend() != "tpu":
        raise ValueError(
            f"use_pallas=True needs a TPU backend, got {jax.default_backend()!r}; "
            'pass use_pallas="interpret" to run the kernel body in interpret mode'
        )
    return (bool(use_pallas), False)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("causal", "use_pallas", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, use_pallas="auto", block_q=128, block_k=128):
    use, interp = _resolve(use_pallas, auto=_on_tpu())
    if use:
        return _flash_pallas(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k, interpret=interp
        )
    return ref.flash_attention_ref(q, k, v, causal=causal)


@partial(jax.jit, static_argnames=("chunk", "use_pallas"))
def ssd_scan(x, dt, a, b, c, *, chunk=128, use_pallas="auto"):
    use, interp = _resolve(use_pallas, auto=_on_tpu())
    if use:
        return _ssd_pallas(x, dt, a, b, c, chunk=chunk, interpret=interp)
    from ..models.ssm import ssd_chunked

    return ssd_chunked(x, dt, a, b, c, chunk=chunk)


@partial(jax.jit, static_argnames=("use_pallas",))
def dequant_normalize(x, mean, std, *, use_pallas="auto"):
    use, interp = _resolve(use_pallas, auto=False)
    if use:
        return _dequant_pallas(x, mean, std, interpret=interp)
    return ref.dequant_normalize_ref(x, mean, std)


@partial(jax.jit, static_argnames=("out_hw", "out_dtype", "use_pallas"))
def dequant_normalize_augment(
    x, mean, std, flip=None, crop=None, *,
    out_hw=None, out_dtype=jnp.bfloat16, use_pallas="auto",
):
    """Fused on-chip decode tail: crop → flip → dequant → normalize → NCHW.

    The device side of the ``uint8_wire`` contract (what
    ``DeviceTransfer(device_decode=...)`` dispatches): uint8 (or [0,1]
    float) NHWC in, normalized ``out_dtype`` NCHW out, fused by XLA into
    one pass.
    """
    use, interp = _resolve(use_pallas, auto=False)
    if use:
        return _dequant_augment_pallas(
            x, mean, std, flip=flip, crop=crop,
            out_hw=out_hw, out_dtype=out_dtype, interpret=interp,
        )
    return ref.dequant_normalize_augment_ref(
        x, mean, std, flip=flip, crop=crop,
        out_hw=out_hw, out_dtype=out_dtype,
    )
