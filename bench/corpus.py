"""The benchmark's image corpus: records made from a seed, written as files.

One general generator reads a traffic file (``bench/traffic/<name>.json``):
its table of record sizes, its content model and its sampler settings.
Every seed gets the same multiset of sizes (counts fixed by the table's
weights), assigned to records in a seeded order, so seeds change which
record is which and not how much work there is.

A record's pixels are a pure function of ``(seed, index, size)``: a smooth
bilinear field with a base colour, the low bits cleared, plus noise taken
from a per-run pool.  ``record_pixels`` is what the reference decode reads
as the truth; ``write_corpus`` encodes the same pixels with the program's
codec into an ``ArrayDataset`` directory (one file per record, an
``index.txt``), in parallel threads, one record at a time;
``encode_records`` encodes them alike into memory.
"""

from __future__ import annotations

import concurrent.futures as cf
import pathlib

import numpy as np

#: bytes of noise drawn once per run; a record takes a window of it
NOISE_POOL_BYTES = 8 << 20


def seed_words(seed: int, *tags: int) -> list[int]:
    """Entropy for ``np.random.default_rng``: any non-negative seed, any size."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return [int(seed), *tags]


def record_sizes(traffic: dict, n: int, seed: int) -> np.ndarray:
    """(n, 2) int heights and widths: the table's fixed counts, seeded order."""
    rows = traffic["record_sizes"]["rows"]
    weights = np.array([r[2] for r in rows], np.float64)
    weights /= weights.sum()
    counts = np.floor(weights * n).astype(np.int64)
    short = n - int(counts.sum())
    # the remainder goes to the largest fractional parts (seed-independent)
    order = np.argsort(-(weights * n - counts), kind="stable")
    counts[order[:short]] += 1
    sizes = np.repeat(np.array([[r[0], r[1]] for r in rows], np.int64), counts, axis=0)
    perm = np.random.default_rng(seed_words(seed, 1)).permutation(n)
    return sizes[perm]


_INTERP: dict[tuple[int, int], np.ndarray] = {}


def _interp(n: int, cell: int) -> np.ndarray:
    """(n, n // cell + 2) bilinear weights from grid nodes to pixels."""
    key = (n, cell)
    a = _INTERP.get(key)
    if a is None:
        pos = np.arange(n) / cell
        j = np.floor(pos).astype(np.int64)
        t = (pos - j).astype(np.float32)
        a = np.zeros((n, n // cell + 2), np.float32)
        a[np.arange(n), j] = 1 - t
        a[np.arange(n), j + 1] = t
        _INTERP[key] = a
    return a


def noise_pool(traffic: dict, seed: int) -> np.ndarray:
    amp = int(traffic["content"]["noise_amplitude"])
    rng = np.random.default_rng(seed_words(seed, 2))
    return rng.integers(0, amp + 1, NOISE_POOL_BYTES, dtype=np.uint8)


def record_pixels(traffic: dict, seed: int, i: int, hw, pool: np.ndarray) -> np.ndarray:
    """Record ``i``'s (h, w, 3) uint8 pixels."""
    content = traffic["content"]
    cell, amp = int(content["cell_px"]), int(content["field_amplitude"])
    noise = int(content["noise_amplitude"])
    keep = np.uint8((0xFF << int(content["quantize_bits"])) & 0xFF)
    h, w = int(hw[0]), int(hw[1])
    rng = np.random.default_rng(seed_words(seed, 3, i))
    ay, ax = _interp(h, cell), _interp(w, cell)
    grid = rng.random((ay.shape[1], ax.shape[1], 3), dtype=np.float32) * amp
    base = rng.integers(0, 256 - amp - noise, 3).astype(np.float32)
    offset = int(rng.integers(0, pool.size - h * w * 3 + 1))
    rows = np.tensordot(ay, grid, axes=(1, 0))  # (h, gw, 3)
    field = np.matmul(ax, rows)  # (h, w, 3), C-contiguous
    field += base
    img = field.astype(np.uint8)
    img &= keep
    img += pool[offset : offset + h * w * 3].reshape(h, w, 3)
    return img


def encoder(traffic: dict, n: int, seed: int):
    """(sizes, ``encode(i)``): the records' (n, 2) sizes, and record ``i``
    encoded with the program's codec."""
    from repro.data.codec import encode_sample

    sizes = record_sizes(traffic, n, seed)
    pool = noise_pool(traffic, seed)
    return sizes, lambda i: encode_sample(record_pixels(traffic, seed, i, sizes[i], pool))


def write_corpus(root, traffic: dict, n: int, seed: int, threads: int) -> dict:
    """Write ``n`` records and ``index.txt`` under ``root``; returns sizes
    and the raw and encoded byte counts."""
    root = pathlib.Path(root)
    root.mkdir(parents=True, exist_ok=True)
    sizes, encode = encoder(traffic, n, seed)
    names = [f"{i:06d}.rpr" for i in range(n)]

    def one(i: int) -> int:
        data = encode(i)
        (root / names[i]).write_bytes(data)
        return len(data)

    with cf.ThreadPoolExecutor(max_workers=threads) as ex:
        encoded = sum(ex.map(one, range(n)))
    (root / "index.txt").write_text("\n".join(names))
    raw = int((sizes[:, 0] * sizes[:, 1]).sum() * 3)
    return {"sizes": sizes, "raw_bytes": raw, "encoded_bytes": int(encoded)}


def encode_records(traffic: dict, n: int, seed: int, threads: int) -> list[bytes]:
    """The ``n`` records ``write_corpus`` would write, encoded in memory."""
    _, encode = encoder(traffic, n, seed)
    with cf.ThreadPoolExecutor(max_workers=threads) as ex:
        return list(ex.map(encode, range(n)))
