"""The corpus generator and the reference decode.

Run explicitly: ``python -m pytest bench/tests``.
"""

from __future__ import annotations

import json
import pathlib

import ml_dtypes
import numpy as np
import pytest

from bench import corpus, reference

TRAFFIC = json.loads(
    (pathlib.Path(__file__).resolve().parents[1] / "traffic" / "mixed_sizes.json").read_text()
)


def test_same_seed_same_files(tmp_path):
    a = corpus.write_corpus(tmp_path / "a", TRAFFIC, 24, 3_000_000_001, threads=4)
    b = corpus.write_corpus(tmp_path / "b", TRAFFIC, 24, 3_000_000_001, threads=2)
    c = corpus.write_corpus(tmp_path / "c", TRAFFIC, 24, 3_000_000_002, threads=4)
    names = (tmp_path / "a" / "index.txt").read_text().split()
    assert len(names) == 24
    same = [(tmp_path / "a" / n).read_bytes() == (tmp_path / "b" / n).read_bytes() for n in names]
    other = [(tmp_path / "a" / n).read_bytes() == (tmp_path / "c" / n).read_bytes() for n in names]
    assert all(same) and not any(other)
    assert a["encoded_bytes"] == b["encoded_bytes"] != c["encoded_bytes"]


def test_records_in_memory_are_the_files(tmp_path):
    corpus.write_corpus(tmp_path, TRAFFIC, 16, 3_000_000_001, threads=3)
    blobs = corpus.encode_records(TRAFFIC, 16, 3_000_000_001, threads=2)
    assert blobs == [(tmp_path / f"{i:06d}.rpr").read_bytes() for i in range(16)]


@pytest.mark.parametrize("n", [100, 2048])
def test_every_seed_gets_the_size_table(n):
    rows = TRAFFIC["record_sizes"]["rows"]
    want = {(h, w): wt * n for h, w, wt in rows}
    counts = []
    for seed in (1, 2_147_483_749, 9_000_000_000):
        sizes = corpus.record_sizes(TRAFFIC, n, seed)
        got = {}
        for h, w in sizes.tolist():
            got[(h, w)] = got.get((h, w), 0) + 1
        assert set(got) <= set(want)
        assert all(abs(got.get(k, 0) - v) < 1 for k, v in want.items())
        counts.append(got)
    # the same multiset for every seed, in another order
    assert counts[0] == counts[1] == counts[2]
    assert not np.array_equal(corpus.record_sizes(TRAFFIC, n, 1), corpus.record_sizes(TRAFFIC, n, 2))


def test_content_is_compressible_but_not_trivial(tmp_path):
    made = corpus.write_corpus(tmp_path, TRAFFIC, 64, 7, threads=4)
    ratio = made["raw_bytes"] / made["encoded_bytes"]
    # the traffic file records the ratio measured at full size
    assert 1.8 < ratio < 3.0
    img = corpus.record_pixels(TRAFFIC, 7, 0, made["sizes"][0], corpus.noise_pool(TRAFFIC, 7))
    assert len(np.unique(img)) >= 16  # many levels, not a flat fill


def test_reference_reads_the_truth_from_the_records(tmp_path):
    """The program's own decode of a record equals the reference's truth."""
    from repro.data.codec import decode_sample

    corpus.write_corpus(tmp_path, TRAFFIC, 8, 11, threads=2)
    truth = reference.Truth(TRAFFIC, 8, 11)
    for i in range(8):
        got = decode_sample((tmp_path / f"{i:06d}.rpr").read_bytes())
        np.testing.assert_array_equal(got, truth.pixels(i))


@pytest.mark.parametrize("shuffle", [True, False])
def test_resize_and_sampler_order_match_the_program(shuffle):
    from repro.data.codec import resize_nearest
    from repro.data.sampler import CheckpointableSampler

    img = np.arange(375 * 500 * 3, dtype=np.uint32).reshape(375, 500, 3)
    np.testing.assert_array_equal(reference.resize_nearest(img, (256, 256)),
                                  resize_nearest(img, (256, 256)))
    s = CheckpointableSampler(64, batch_size=16, seed=3_000_000_003, shuffle=shuffle)
    it = iter(s)
    for k in range(9):  # across two epoch boundaries
        want = reference.batch_records(k, 64, 16, 3_000_000_003, shuffle)
        assert next(it) == want.tolist()


def test_bf16_ulps():
    want = np.array([1.0, -2.0, 0.5], np.float32).astype(ml_dtypes.bfloat16)
    assert reference.bf16_ulps(want, want) == 0.0
    got = np.array([1.0078125, -2.0, 0.5], np.float32).astype(ml_dtypes.bfloat16)
    assert reference.bf16_ulps(got, want) == pytest.approx(1.0)
    assert reference.bf16_ulps(got[:2], want) == float("inf")
