"""Run the image training path once on the TPU and check what comes out.

The path is the one users run: ``SyntheticImageDataset`` (256² uint8
images, made from a seed) → ``build_image_loader(uint8_wire=True,
device_decode=DeviceDecode(...))`` → ``device_put`` + the on-chip decode
(crop to 224², flip, normalize, bf16 NCHW) → ten steps of a ViT-B/16
training step at its published widths, batch 128, random weights.

Checks (any failure exits nonzero):

* every loss is finite;
* no loader stage counted a failed item;
* after the slab ring has been recycled three times, one delivered
  decoded batch equals a numpy reference of the same samples under the
  same seeded crop and flip draws.

``--four-chips`` runs only the data-parallel phase: the loader places
each global batch of 512 with ``NamedSharding(Mesh(4 devices), P("data"))``,
each chip holds its own 128 rows, the parameters are replicated, and the
loss of the first step must agree with the same batch computed one shard
at a time on single devices.

The script runs on a TPU or not at all: with no TPU it exits nonzero
before any work.  The lines it prints before the last are informational
(compile seconds, step time, input wait), not a benchmark.  The last line
is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips of one host
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from benchmarks import vit  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.data import SyntheticImageDataset, build_image_loader  # noqa: E402
from repro.data.codec import decode_sample  # noqa: E402
from repro.data.transfer import DeviceDecode  # noqa: E402

MEAN = (0.485, 0.456, 0.406)  # ImageNet per-channel stats, [0, 1] units
STD = (0.229, 0.224, 0.225)
HW, OUT_HW = (256, 256), (224, 224)
BATCH = 128  # per chip: the compiler's memory analysis puts ViT-B/16 at ~12.4 GB
STEPS = 10
FOUR_CHIP_STEPS = 3
SEED = 0
RECYCLES = 3  # the checked batch comes after the slab ring went round this often
# bf16 keeps 8 significant bits: device and numpy round the same f32 math,
# which may differ in its last f32 bit, so allow one bf16 step either way
BF16_RTOL = 2.0**-7


def info(msg: str) -> None:
    print(f"info (not a benchmark): {msg}", flush=True)


def augment_draws(seed: int, batch: int, hw, out_hw):
    """Yield each batch's (flip, crop) draws in the order ``DeviceTransfer``
    makes them for ``DeviceDecode(flip=True, crop=True)``."""
    rng = np.random.default_rng(seed)
    (h, w), (oh, ow) = hw, out_hw
    while True:
        flip = rng.integers(0, 2, batch, dtype=np.int32)
        crop = np.stack(
            [
                rng.integers(0, h - oh + 1, batch, dtype=np.int32),
                rng.integers(0, w - ow + 1, batch, dtype=np.int32),
            ],
            axis=1,
        )
        yield flip, crop


def decode_reference(ds, indices, flip, crop, out_hw) -> np.ndarray:
    """numpy twin of the on-chip decode: crop → flip → /255 → normalize →
    NCHW → bf16, one sample at a time."""
    oh, ow = out_hw
    mean, std = np.asarray(MEAN, np.float32), np.asarray(STD, np.float32)
    rows = []
    for i, f, (top, left) in zip(indices, flip, crop):
        y = decode_sample(ds.read_bytes(int(i)))[top : top + oh, left : left + ow]
        if f:
            y = y[:, ::-1]
        y = y.astype(np.float32) * np.float32(1.0 / 255.0)
        rows.append(((y - mean) / std).transpose(2, 0, 1))
    return np.stack(rows).astype(jnp.bfloat16)


def check_decoded(got, want) -> float:
    """Raise unless ``got`` (device batch) matches the numpy reference
    within one bf16 rounding step; returns the largest absolute error."""
    got = np.asarray(jax.device_get(got), np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"decoded batch shape {got.shape} != reference {want.shape}")
    err = np.abs(got - want)
    bad = err > BF16_RTOL * np.abs(want) + 1e-6
    if bad.any():
        raise AssertionError(
            f"decoded batch differs from the numpy reference at {int(bad.sum())} "
            f"of {bad.size} values (max abs error {float(err.max()):.4g})"
        )
    return float(err.max())


def check_shards(cfg, params, x, labels, dp_loss, mesh, rows: int) -> float:
    """Each device holds its own ``rows`` rows of ``x``; the loss of the
    data-parallel step equals the mean of per-shard losses computed one
    device at a time.  Returns the relative loss gap."""
    shards = sorted(x.addressable_shards, key=lambda s: s.index[0].start)
    devices = list(mesh.devices.flat)
    if len(shards) != len(devices) or {s.device for s in shards} != set(devices):
        raise AssertionError(f"batch spans {[s.device for s in shards]}, mesh {devices}")
    one_loss = jax.jit(lambda p, xs, ls: vit.loss(cfg, p, xs, ls))
    losses = []
    for k, s in enumerate(shards):
        if s.index[0] != slice(k * rows, (k + 1) * rows) or s.data.shape[0] != rows:
            raise AssertionError(f"device {s.device} holds rows {s.index[0]}, not its own {rows}")
        p_d = jax.device_put(params, s.device)
        l_d = jax.device_put(labels[k * rows : (k + 1) * rows], s.device)
        losses.append(float(one_loss(p_d, s.data, l_d)))
    ref = float(np.mean(losses))
    gap = abs(float(dp_loss) - ref) / abs(ref)
    if not gap <= BF16_RTOL:
        raise AssertionError(
            f"data-parallel loss {float(dp_loss)} vs shard-by-shard {ref} (rel gap {gap:.3g})"
        )
    return gap


def run_main_path(
    cfg: vit.ViTConfig,
    *,
    batch: int = BATCH,
    hw=HW,
    out_hw=OUT_HW,
    steps: int = STEPS,
    seed: int = SEED,
    mesh=None,
) -> dict:
    """Loader → on-chip decode → ``steps`` ViT steps, with every check.

    ``batch`` is the global batch; with ``mesh`` (one axis, ``"data"``)
    the loader shards it across the mesh and the parameters are
    replicated.  Raises ``AssertionError`` when a check fails."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    shardings = replicated = None
    if mesh is not None:
        shardings = NamedSharding(mesh, P("data"))
        replicated = NamedSharding(mesh, P())
    n_samples = 2 * batch + batch // 2  # >= 2 batches; later batches wrap epochs
    dd = DeviceDecode(MEAN, STD, out_hw=out_hw, crop=True, flip=True, seed=seed)
    out: dict = {}
    with tempfile.TemporaryDirectory() as d:
        ds = SyntheticImageDataset.materialize(d, n_samples, hw=hw, seed=seed)
        params = vit.init(cfg, jax.random.PRNGKey(seed), out_hw)
        labels = jnp.asarray(np.arange(batch) % cfg.n_classes, jnp.int32)
        if mesh is not None:
            params = jax.device_put(params, replicated)
            labels = jax.device_put(labels, shardings)
        params0 = params
        pipe = build_image_loader(
            ds, batch_size=batch, hw=hw, uint8_wire=True, device_decode=dd,
            epochs=None, shardings=shardings,
        )
        stash: deque = deque()
        delivered = 0
        wait = 0.0

        def next_batch():
            nonlocal delivered, wait
            if not stash:
                t0 = time.monotonic()
                stash.extend(pipe.get_items(2))
                wait += time.monotonic() - t0
            delivered += 1
            return stash.popleft()["images"]

        with pipe.auto_stop():
            t0 = time.monotonic()
            pipe.start()
            first = next_batch()
            out["first_batch_s"] = time.monotonic() - t0
            t0 = time.monotonic()
            step = vit.make_step(cfg).lower(params, first, labels).compile()
            out["compile_s"] = time.monotonic() - t0
            losses = []
            x = first
            wait = 0.0
            t0 = time.monotonic()
            for k in range(steps):
                if k:
                    x = next_batch()
                params, loss = step(params, x, labels)
                losses.append(loss)
            jax.block_until_ready(params)
            out["step_s"] = (time.monotonic() - t0) / steps
            out["input_wait_s"] = wait
            out["losses"] = [float(v) for v in jax.device_get(losses)]
            if not np.all(np.isfinite(out["losses"])):
                raise AssertionError(f"non-finite loss: {out['losses']}")
            if mesh is not None:
                out["shard_loss_gap"] = check_shards(
                    cfg, params0, first, labels, out["losses"][0], mesh,
                    batch // mesh.size,
                )

            rows = {s.name: s for s in pipe.stats()}
            check_at = max(delivered, RECYCLES * rows["batch"].num_slabs)
            while delivered <= check_at:
                x = next_batch()
            draws = augment_draws(seed, batch, hw, out_hw)
            for _ in range(check_at):
                next(draws)
            flip, crop = next(draws)
            indices = (check_at * batch + np.arange(batch)) % n_samples
            want = decode_reference(ds, indices, flip, crop, out_hw)
            out["decode_max_abs_err"] = check_decoded(x, want)
            out["checked_batch"] = check_at
            out["num_slabs"] = rows["batch"].num_slabs

            snaps = pipe.stats()
        failed = {s.name: s.num_failed for s in snaps if s.num_failed}
        if failed:
            raise AssertionError(f"loader stages counted failures: {failed}")
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run only the data-parallel phase on four chips",
    )
    args = parser.parse_args(argv)
    cache_dir = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX reports platform {dev.platform!r}); "
            "this check runs only on a TPU",
            file=sys.stderr,
        )
        return 2
    info(f"device_kind={dev.device_kind} count={len(devices)} compile_cache={cache_dir}")
    mesh = None
    batch = BATCH
    steps = STEPS
    if args.four_chips:
        if len(devices) < 4:
            print(f"chip_smoke: --four-chips needs 4 TPU chips, found {len(devices)}",
                  file=sys.stderr)
            return 2
        from jax.sharding import Mesh

        mesh = Mesh(np.array(devices[:4]), ("data",))
        batch, steps = 4 * BATCH, FOUR_CHIP_STEPS
    t0 = time.monotonic()
    r = run_main_path(vit.VIT_B16, batch=batch, steps=steps, mesh=mesh)
    info(
        f"ViT-B/16 batch={batch} steps={steps}: compile {r['compile_s']:.2f}s, "
        f"first batch {r['first_batch_s']:.2f}s, step {r['step_s'] * 1e3:.1f}ms, "
        f"input wait {r['input_wait_s']:.4f}s over the steps"
    )
    info(f"losses {r['losses']}")
    info(
        f"decoded batch {r['checked_batch']} (slab ring of {r['num_slabs']}) matches "
        f"the numpy reference, max abs err {r['decode_max_abs_err']:.4g}"
    )
    if mesh is not None:
        info(f"4-way data parallel: first-step loss vs shard-by-shard rel gap "
             f"{r['shard_loss_gap']:.3g}")
    info(f"total {time.monotonic() - t0:.1f}s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
