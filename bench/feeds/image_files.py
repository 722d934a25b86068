"""Image records, one codec file per record, through the image loader.

The corpus is ``corpus.write_corpus``: an ``ArrayDataset`` directory.  The
pipeline is ``build_image_loader`` as a user states it for ImageNet
training (host resize to the configuration's ``hw``, uint8 on the wire,
the on-chip crop, flip and normalize), every other tunable at the
program's default.  The check decodes the same batches with
``bench/reference.py`` and counts the largest gap in bfloat16 ulps.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from bench import corpus, reference

fields = ("images",)


def write(workdir, config: dict, traffic: dict, n: int, seed: int, threads: int) -> None:
    corpus.write_corpus(workdir, traffic, n, seed, threads=threads)


def build(workdir, config: dict, traffic: dict, *, batch: int, seed: int, shardings, sampler):
    from repro.data import ArrayDataset

    return image_loader(ArrayDataset(workdir), config, batch=batch, seed=seed,
                        shardings=shardings, sampler=sampler)


def image_loader(dataset, config: dict, *, batch: int, seed: int, shardings, sampler, **more):
    """``build_image_loader`` over ``dataset`` with the configuration's
    decode; ``more`` goes to the loader as it is (a projection)."""
    from repro.data import build_image_loader
    from repro.data.transfer import DeviceDecode

    lcfg = config["loader"]
    return build_image_loader(
        dataset, batch_size=batch, hw=tuple(lcfg["hw"]), uint8_wire=True,
        device_decode=DeviceDecode(
            tuple(lcfg["mean"]), tuple(lcfg["std"]), out_hw=tuple(lcfg["out_hw"]),
            crop=bool(lcfg["crop"]), flip=bool(lcfg["flip"]), seed=seed,
        ),
        epochs=None, shardings=shardings, sampler=sampler, **more,
    )


def reference_batches(config: dict, traffic: dict, *, n: int, batch: int, seed: int,
                      shuffle: bool):
    """``batch_at(k, dtype)``: the reference decode of the stream's ``k``-th
    batch, from each record's true pixels."""
    truth = reference.Truth(traffic, n, seed)
    lcfg = config["loader"]

    def batch_at(k: int, dtype=np.float32) -> np.ndarray:
        return reference.decode_batch(
            truth, k, n=n, batch=batch, seed=seed, hw=tuple(lcfg["hw"]),
            out_hw=tuple(lcfg["out_hw"]), mean=lcfg["mean"], std=lcfg["std"],
            shuffle=shuffle, dtype=dtype,
        )

    return batch_at


def check(sample, batch_at, calibrate: bool) -> tuple[dict, dict]:
    """``decode_err_ulp`` over the sampled batches; with ``calibrate``, the
    control's: the reference computed in bfloat16."""
    numbers = {"decode_err_ulp": max(reference.bf16_ulps(x, batch_at(k)) for k, x in sample)}
    readings: dict = {}
    if calibrate:
        readings["control"] = {"decode_err_ulp": max(
            reference.bf16_ulps(batch_at(k, ml_dtypes.bfloat16), batch_at(k))
            for k, _ in sample)}
    return numbers, readings
