"""Device transfer stage (paper §5.7.2, adapted to JAX/TPU — DESIGN §2).

``DeviceTransfer`` is the terminal pipe stage: it places a host batch onto
devices with the training step's input sharding via ``jax.device_put`` —
JAX dispatches asynchronously, so with the pipeline keeping ≥1 batch in the
sink the H2D copy overlaps the running step (the CUDA-side "separate
stream" of the paper).  Per §2.1 there must be at most ONE transfer task:
build the stage with ``concurrency=1`` (the loader does).

``uint8_wire=True`` makes uint8 the end-to-end wire contract: loaders ship
uint8 payloads (slab rows arrive uint8 already and pass through untouched,
zero copies), float image payloads that slipped into the batch are
downcast from [0, 1] — out-of-range floats raise instead of silently
clipping — and the device side expands to bf16 on-chip.  4× fewer
host→device bytes than f32 (beyond-paper optimization,
kernels/dequant_normalize.py).  Integer payloads pass through untouched.

``device_decode=DeviceDecode(mean, std, ...)`` finishes the decode ON the
accelerator: right after ``device_put`` the transfer dispatches the fused
``dequant_normalize_augment`` (uint8→bf16 dequant, per-channel normalize,
per-sample flip/crop augment, one XLA fusion, NCHW out), so the
host-side path never touches a pixel float — augment draws are tiny int
arrays from a seeded numpy generator.  Dispatch cost is counted in
``device_decode_ms`` (the kernel itself runs async on the device) and
surfaces on the transfer stage's stats row via the ``stats()`` probe.

Chunked dispatch: ``transfer_many`` is the vectorized-chunk twin of
``__call__`` — the engine hands it the batches a sink-side ``get_many``
drained and it issues their transfers back-to-back in arrival order.
Double buffering is shared with the per-batch path: each dispatched slab
enters the same hold ring, so slab *k* is recycled only after the whole
consumer window has moved past it, chunked or not.

Double buffering (zero-copy arena path): a batch arriving from an
``aggregate_into`` stage carries its owning slab under ``SLAB_KEY``.  The
slab's host memory must stay intact until nothing reads it anymore, so the
transfer keeps a ring of "staging" slabs — the last ``hold_slabs`` batches
— and releases the oldest back to the arena only as new transfers are
issued.

``hold_slabs`` defaults to ``consumer_window + 1 + dispatch_chunk``:
enough to cover every batch that can be live at once (the sink buffer +
the batch the consumer holds + one mid-handoff + the rest of a chunked
dispatch still un-put in the worker; ``dispatch_chunk=1`` recovers the
classic ``consumer_window + 2``).  That window matters because ``jax.device_put``
may *alias* host numpy memory instead of snapshotting it — and whether it
does is a per-buffer size/alignment decision inside XLA (small arrays get
copied, slab-sized ones get aliased on CPU), so it cannot be probed
reliably once up front.  Holding the full window is a few batch-buffers of
host memory; releasing early is silent data corruption.  Consumers that
retain batches beyond the current iteration must copy them.  No
``block_until_ready()`` ever enters the hot path.

Tracing (only while the transfer's tracer is enabled, see ``core.trace``):
each copy is one ``h2d`` span from its ``device_put`` call until the put
array is resident.  Residency is seen by one watcher thread per transfer,
which blocks on each put array in ``device_put`` order (the array before
the on-chip decode, which does not donate it), off the hot path; the
thread starts on the first traced call and ``flush()`` stops it.
``h2d_unresident_releases`` counts the slabs the hold ring released
before the watcher had seen their copy resident, on the transfer stage's
stats row via ``stats()``.  With tracing off no thread starts and the
counter stays 0.
"""

from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..core import trace as _trace
from .arena import SLAB_KEY

logger = logging.getLogger("repro.data")

#: absolute slack allowed past [0, 1] before a float wire payload is
#: rejected — covers resize/antialias ringing, not wrong normalization
_WIRE_EPS = 1e-3


def to_uint8_wire(v: Any) -> Any:
    """Downcast a [0,1]-normalized float image payload to the uint8 wire
    format (inverse of the on-chip ``x/255`` dequant).

    Already-uint8 arrays pass through unchanged — the zero-copy slab path
    ships uint8 natively and must not pay a copy here.  Float image
    payloads outside [0, 1] (beyond a tiny epsilon) raise ``ValueError``:
    silently clipping them would corrupt every pixel the consumer trains
    on, loudly is the only acceptable failure mode.  Anything that is not
    a floating-point image-shaped array passes through unchanged.
    """
    if (
        isinstance(v, np.ndarray)
        and v.dtype in (np.float32, np.float64)
        and v.ndim >= 3  # (H, W, C) or (N, H, W, C): image-like payloads only
    ):
        if v.size:
            lo, hi = float(v.min()), float(v.max())
            if lo < -_WIRE_EPS or hi > 1.0 + _WIRE_EPS:
                raise ValueError(
                    f"uint8_wire expects [0,1]-normalized floats "
                    f"(normalize_to_float convention); got range [{lo:.4g}, "
                    f"{hi:.4g}] — normalize on-chip via device_decode "
                    "instead of pre-scaling on the host"
                )
        return np.clip(np.rint(v * 255.0), 0.0, 255.0).astype(np.uint8)
    return v


@dataclasses.dataclass(frozen=True)
class DeviceDecode:
    """Config for the on-chip fused decode tail behind ``DeviceTransfer``.

    ``mean``/``std`` are per-channel (C,) stats in [0,1] units (the
    ImageNet convention).  ``out_hw`` crops every sample to a static
    window (random per-sample offsets when ``crop=True``, centered
    otherwise); ``flip=True`` mirrors each sample with p=0.5.  Augment
    randomness comes from a seeded numpy generator on the host — integer
    draws only, the pixels themselves are never touched host-side.
    """

    mean: tuple[float, ...]
    std: tuple[float, ...]
    field: str = "images"  # batch key holding (N, H, W, C) wire payloads
    out_hw: tuple[int, int] | None = None  # None = full frame
    flip: bool = False  # random horizontal flip (p=0.5)
    crop: bool = False  # random (vs centered) out_hw window placement
    out_dtype: Any = jnp.bfloat16
    seed: int = 0
    use_pallas: Any = "auto"  # "auto" | True | "interpret" | False


class _ResidencyWatcher:
    """One thread that sees each traced copy resident, in put order: it
    blocks on each put array and records the copy's ``h2d`` span."""

    def __init__(self):
        self.resident = 0  # highest batch number seen resident
        self._pending: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(target=self._run, name="h2d-watcher", daemon=True)
        self._thread.start()

    def watch(self, tracer, put: Any, t0: float, nbytes: int, batch: int) -> None:
        self._pending.put((tracer, put, t0, nbytes, batch))

    def _run(self) -> None:
        while (item := self._pending.get()) is not None:
            tracer, put, t0, nbytes, batch = item
            try:
                jax.block_until_ready(put)
            except Exception:  # the consumer sees the failure; keep watching
                logger.warning("h2d watcher: batch %d failed its copy", batch, exc_info=True)
                continue
            t1 = time.monotonic()
            tracer.complete("h2d", "transfer", t0, t1 - t0, {"bytes": nbytes, "batch": batch})
            self.resident = batch

    def stop(self) -> None:
        """Wait for every watched copy, then end the thread."""
        self._pending.put(None)
        self._thread.join()


class DeviceTransfer:
    def __init__(
        self,
        shardings: Any | None = None,
        *,
        uint8_wire: bool = False,
        hold_slabs: int | None = None,
        consumer_window: int = 3,
        dispatch_chunk: int = 1,
        device_decode: DeviceDecode | None = None,
        tracer=None,
    ):
        if hold_slabs is None:
            # consumer window + the batch mid-handoff + every batch of the
            # current dispatch chunk still un-put in the worker (chunked
            # transfer_many issues the whole chunk before put_many runs)
            hold_slabs = consumer_window + 1 + max(1, dispatch_chunk)
        self.shardings = shardings
        self.uint8_wire = uint8_wire
        self.hold_slabs = hold_slabs  # slabs kept alive behind the current one
        self.device_decode = device_decode
        self.bytes_moved = 0
        self.num_batches = 0
        # fused on-chip decode accounting (host-side dispatch cost only —
        # the kernel runs async); surfaced via stats() → the stage probe
        self.device_decode_ms = 0.0
        self.device_decode_batches = 0
        self.h2d_unresident_releases = 0
        # explicit tracer, else whatever is installed process-wide at call
        # time (host→device spans land on the watcher thread's track)
        self._tracer = tracer
        self._watcher: _ResidencyWatcher | None = None
        # (slab, batch number whose copy reads it; 0 = copy not watched)
        self._held: deque[tuple[Any, int]] = deque()
        if device_decode is not None:
            self._decode_mean = jnp.asarray(device_decode.mean, jnp.float32)
            self._decode_std = jnp.asarray(device_decode.std, jnp.float32)
            self._decode_rng = np.random.default_rng(device_decode.seed)

    def __call__(self, batch: Any) -> Any:
        slab = None
        if isinstance(batch, dict):
            slab = batch.pop(SLAB_KEY, None)
            if self.uint8_wire:
                batch = {k: to_uint8_wire(v) for k, v in batch.items()}
        nbytes = (
            sum(v.nbytes for v in batch.values() if hasattr(v, "nbytes"))
            if isinstance(batch, dict)
            else getattr(batch, "nbytes", 0)
        )
        self.bytes_moved += nbytes
        self.num_batches += 1
        tracer = self._tracer if self._tracer is not None else _trace.get_tracer()
        watched = self.num_batches if tracer.enabled else 0
        t0 = time.monotonic() if watched else 0.0
        if self.shardings is None:
            out = jax.device_put(batch)
        else:
            out = jax.device_put(batch, self.shardings)
        if watched:
            watcher = self._watcher
            if watcher is None:
                watcher = self._watcher = _ResidencyWatcher()
            watcher.watch(tracer, out, t0, nbytes, watched)
        out = self._maybe_decode(out, tracer)
        if slab is not None:
            # The copy for `slab` is now in flight; recycle the one from
            # hold_slabs batches ago, whose copy is certainly consumed.
            self._held.append((slab, watched))
            while len(self._held) > self.hold_slabs:
                self._release(*self._held.popleft())
        return out

    def _release(self, slab: Any, batch: int) -> None:
        watcher = self._watcher
        if watcher is not None and batch > watcher.resident:
            self.h2d_unresident_releases += 1
        slab.release()

    def transfer_many(self, batches: list) -> list:
        """Vectorized-chunk entry point: dispatch a drained chunk of batches
        back-to-back, in order (wire as ``pipe(transfer.transfer_many,
        chunk=N, vectorized=True)``).  One executor call issues the whole
        chunk's ``device_put`` (+ fused decode) calls; the slab hold ring
        advances per batch exactly as on the per-item path.  The hold
        window must cover the chunk: up to ``len(batches) - 1`` results sit
        un-put in the worker while the chunk's tail is dispatched, so
        construct the transfer with ``dispatch_chunk=`` matching the
        stage's chunk (the loaders do) — an undersized window releases
        slabs the sink still aliases.
        """
        return [self(b) for b in batches]

    def _maybe_decode(self, out: Any, tracer) -> Any:
        """Dispatch the fused on-chip decode for the configured field."""
        dd = self.device_decode
        if dd is None or not isinstance(out, dict) or dd.field not in out:
            return out
        from ..kernels.ops import dequant_normalize_augment

        x = out[dd.field]
        n, h, w, _c = x.shape
        oh, ow = dd.out_hw if dd.out_hw is not None else (h, w)
        flip = crop = None
        if dd.flip:
            flip = self._decode_rng.integers(0, 2, n, dtype=np.int32)
        if oh != h or ow != w:
            if dd.crop:
                crop = np.stack(
                    [
                        self._decode_rng.integers(0, h - oh + 1, n, dtype=np.int32),
                        self._decode_rng.integers(0, w - ow + 1, n, dtype=np.int32),
                    ],
                    axis=1,
                )
            else:
                crop = np.tile(
                    np.array([[(h - oh) // 2, (w - ow) // 2]], np.int32), (n, 1)
                )
        t0 = time.monotonic()
        decoded = dequant_normalize_augment(
            x, self._decode_mean, self._decode_std, flip, crop,
            out_hw=dd.out_hw, out_dtype=dd.out_dtype,
            use_pallas=dd.use_pallas,
        )
        dt = time.monotonic() - t0
        self.device_decode_ms += dt * 1e3
        self.device_decode_batches += 1
        if tracer.enabled:
            tracer.complete(
                "device_decode", "transfer", t0, dt,
                {"batch": self.num_batches, "out_hw": [oh, ow]},
            )
        out = dict(out)
        out[dd.field] = decoded
        return out

    def stats(self) -> dict[str, float]:
        """Probe dict for the transfer stage's stats row (wire with
        ``pipe(..., cache=transfer)`` — the snapshot pulls these keys)."""
        return {
            "device_decode_ms": self.device_decode_ms,
            "device_decode_batches": self.device_decode_batches,
            "h2d_unresident_releases": self.h2d_unresident_releases,
        }

    def flush(self) -> None:
        """Stop the residency watcher once every watched copy is resident,
        then release every held slab (end of stream / teardown).  Callers
        must ensure pending transfers are consumed (e.g. the pipeline
        drained)."""
        watcher, self._watcher = self._watcher, None
        if watcher is not None:
            watcher.stop()
        while self._held:
            self._held.popleft()[0].release()
