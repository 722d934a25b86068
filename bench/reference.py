"""Plain reference of the image input path, and the comparison that uses it.

It imports nothing of the program.  From the seed alone it works out which
records make up batch ``k`` (the sampler's seeded shuffle, epoch by
epoch), the crop and flip draws of that batch (the transfer's seeded
generator, batch by batch), and the decoded batch itself, from each
record's true pixels (``corpus.record_pixels``): nearest resize to the
wire size, crop, flip, ``/255``, per-channel normalize, NCHW, bfloat16.

``decode_batch(..., dtype=ml_dtypes.bfloat16)`` is the control: the same
arithmetic with every operation rounded to bfloat16.
"""

from __future__ import annotations

import concurrent.futures as cf

import ml_dtypes
import numpy as np

from bench import corpus


def epoch_order(n: int, seed: int, epoch: int, shuffle: bool = True) -> np.ndarray:
    """The sampler's order of ``n`` records in ``epoch``."""
    idx = np.arange(n, dtype=np.int64)
    if shuffle:
        np.random.default_rng([seed, epoch]).shuffle(idx)
    return idx


def batch_records(k: int, n: int, batch: int, seed: int, shuffle: bool = True) -> np.ndarray:
    """Record indices of the ``k``-th batch of the stream (drop_last epochs)."""
    per_epoch = n // batch
    epoch, pos = divmod(k, per_epoch)
    return epoch_order(n, seed, epoch, shuffle)[pos * batch : (pos + 1) * batch]


def augment_draws(seed: int, k: int, batch: int, hw, out_hw):
    """(flip (B,), crop (B, 2)) of the ``k``-th batch: the transfer draws a
    flip per row, then a top and a left offset per row, batch after batch."""
    rng = np.random.default_rng(seed)
    (h, w), (oh, ow) = hw, out_hw
    for _ in range(k + 1):
        flip = rng.integers(0, 2, batch, dtype=np.int32)
        top = rng.integers(0, h - oh + 1, batch, dtype=np.int32)
        left = rng.integers(0, w - ow + 1, batch, dtype=np.int32)
    return flip, np.stack([top, left], axis=1)


def resize_nearest(img: np.ndarray, hw) -> np.ndarray:
    """Row ``y`` of the output takes source row ``floor(y * ih / h)``."""
    h, w = hw
    ih, iw = img.shape[:2]
    ys = np.arange(h) * ih // h
    xs = np.arange(w) * iw // w
    return img[ys][:, xs]


def decode_row(img, hw, out_hw, flip: int, top: int, left: int, mean, std, dtype):
    """One record's decoded (C, oh, ow) row, computed in ``dtype``."""
    oh, ow = out_hw
    y = resize_nearest(img, hw)[top : top + oh, left : left + ow]
    if flip:
        y = y[:, ::-1]
    y = y.astype(dtype) * dtype(1.0 / 255.0)
    y = (y - np.asarray(mean, dtype)) / np.asarray(std, dtype)
    return y.transpose(2, 0, 1).astype(ml_dtypes.bfloat16)


class Truth:
    """Each record's true pixels, from the seed and the traffic file."""

    def __init__(self, traffic: dict, n: int, seed: int):
        self.traffic, self.seed = traffic, seed
        self.sizes = corpus.record_sizes(traffic, n, seed)
        self.pool = corpus.noise_pool(traffic, seed)

    def pixels(self, i: int) -> np.ndarray:
        return corpus.record_pixels(self.traffic, self.seed, int(i), self.sizes[i], self.pool)


def decode_batch(
    truth: Truth, k: int, *, n: int, batch: int, seed: int, hw, out_hw, mean, std,
    shuffle: bool = True, dtype=np.float32, threads: int = 8,
) -> np.ndarray:
    """The ``k``-th decoded batch, (B, C, oh, ow) bfloat16."""
    records = batch_records(k, n, batch, seed, shuffle)
    flip, crop = augment_draws(seed, k, batch, hw, out_hw)

    def one(j: int) -> np.ndarray:
        return decode_row(
            truth.pixels(records[j]), hw, out_hw, int(flip[j]), int(crop[j, 0]),
            int(crop[j, 1]), mean, std, dtype,
        )

    with cf.ThreadPoolExecutor(max_workers=threads) as ex:
        return np.stack(list(ex.map(one, range(batch))))


def bf16_ulps(got: np.ndarray, want: np.ndarray) -> float:
    """Largest ``|got - want|`` in bfloat16 units in the last place of ``want``."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        return float("inf")
    _, exp = np.frexp(want)
    ulp = np.ldexp(np.float32(1.0), exp - 8)  # bfloat16 keeps 8 significant bits
    err = np.abs(got - want) / ulp
    return float(err.max()) if err.size else 0.0
