"""Zero-copy batch assembly: slab arena, decode-into-slot, aggregate_into,
double-buffered transfer, and the uint8 wire-format downcast."""

import threading
import time

import numpy as np
import pytest

from repro.core import PipelineBuilder
from repro.data import (
    ArenaClosed,
    SlabArena,
    SyntheticImageDataset,
    SyntheticTokenDataset,
    build_image_loader,
    build_lm_loader,
    decode_sample,
    encode_sample,
)
from repro.data.arena import SLAB_KEY
from repro.data.codec import decode_into, parse_header, resize_nearest, resize_nearest_into
from repro.data.packing import SequencePacker
from repro.data.transfer import DeviceTransfer


# ---------------------------------------------------------------------------
# arena primitives
# ---------------------------------------------------------------------------
def test_arena_preallocates_and_recycles():
    a = SlabArena({"x": ((4, 4), np.uint8)}, batch_size=8, num_slabs=3)
    assert a.bytes_allocated == 3 * 8 * 16
    assert a.slabs_in_flight == 0
    s1, s2, s3 = a.acquire(), a.acquire(), a.acquire()
    assert a.slabs_in_flight == 3
    assert a.try_acquire() is None  # ring exhausted, non-blocking path
    buf_id = id(s1.arrays["x"])
    a.release(s1)
    s4 = a.acquire()
    assert id(s4.arrays["x"]) == buf_id  # same memory, recycled
    assert a.acquires == 4
    with pytest.raises(RuntimeError):
        a.release(s4) or a.release(s4)  # double release
    a.release(s2), a.release(s3)


def test_arena_acquire_blocks_and_close_wakes():
    a = SlabArena({"x": ((2,), np.int32)}, batch_size=2, num_slabs=2)
    a.acquire(), a.acquire()
    with pytest.raises(TimeoutError):
        a.acquire(timeout=0.05)
    errs = []

    def blocked():
        try:
            a.acquire()
        except ArenaClosed as e:
            errs.append(e)

    t = threading.Thread(target=blocked)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()  # parked on the ring
    a.close()
    t.join(timeout=5)
    assert not t.is_alive() and len(errs) == 1


# ---------------------------------------------------------------------------
# decode-into-slot codec variants
# ---------------------------------------------------------------------------
def test_decode_into_matches_decode_sample():
    rng = np.random.default_rng(0)
    img = rng.integers(0, 256, (16, 12, 3), dtype=np.uint8)
    data = encode_sample(img)
    out = np.empty((16, 12, 3), np.uint8)
    decode_into(data, out)
    np.testing.assert_array_equal(out, decode_sample(data))
    with pytest.raises(ValueError):
        decode_into(data, np.empty((8, 12, 3), np.uint8))  # shape mismatch
    with pytest.raises(ValueError):
        decode_into(b"XXXX" + data[4:], out)  # corrupt


@pytest.mark.parametrize("container", [bytes, bytearray, memoryview])
@pytest.mark.parametrize("compressor", ["zstd", "zlib"])
def test_decode_reads_payload_in_place(compressor, container):
    """Both decoders read a record from any buffer and either codec, and
    what they return does not alias the record's memory."""
    img = np.random.default_rng(1).integers(0, 256, (9, 14, 3), dtype=np.uint8)
    data = encode_sample(img)
    if compressor == "zlib":
        import zlib

        off = parse_header(data)[2]
        data = data[:off] + zlib.compress(img.tobytes(), 1)
    buf = bytearray(data)
    got = decode_sample(container(buf))
    out = np.empty_like(img)
    decode_into(container(buf), out)
    buf[len(buf) // 2:] = bytes(len(buf) - len(buf) // 2)  # scribble the record
    np.testing.assert_array_equal(got, img)
    np.testing.assert_array_equal(out, img)


def test_resize_nearest_into_matches_resize_nearest():
    rng = np.random.default_rng(1)
    img = rng.integers(0, 256, (37, 53, 3), dtype=np.uint8)
    out = np.empty((16, 24, 3), np.uint8)
    resize_nearest_into(img, out)
    np.testing.assert_array_equal(out, resize_nearest(img, (16, 24)))


# ---------------------------------------------------------------------------
# packer slab emission
# ---------------------------------------------------------------------------
def test_packer_add_into_matches_add():
    rng = np.random.default_rng(2)
    docs = [rng.integers(3, 100, int(rng.integers(4, 40)), dtype=np.int32) for _ in range(12)]
    p_ref, p_slab = SequencePacker(16), SequencePacker(16)
    # nothing releases slabs here, so the ring must cover every emitted row:
    # <= sum(len(doc)) / seq_len rows, comfortably under 16 slabs * 4 rows
    a = SlabArena(
        {k: ((16,), np.int32) for k in ("tokens", "labels", "positions", "segment_ids")},
        batch_size=4,
        num_slabs=16,
    )
    next_slot = a.slot_writer()
    got, want = [], []
    for doc in docs:
        want += p_ref.add(doc)
        got += [r.views() for r in p_slab.add_into(doc.copy(), next_slot)]
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        for k in w:
            np.testing.assert_array_equal(g[k], w[k])


# ---------------------------------------------------------------------------
# aggregate_into through the engine
# ---------------------------------------------------------------------------
def _slot_pipeline(arena, n_items, write, *, agg=4, drop_last=False, **pipe_kw):
    return (
        PipelineBuilder()
        .add_source(range(n_items))
        .pipe(arena.binder(), concurrency=1, name="slot")
        .pipe(write, concurrency=2, name="write", **pipe_kw)
        .aggregate_into(arena, agg, drop_last=drop_last, name="batch")
        .add_sink(buffer_size=2)
        .build(num_threads=4)
    )


def _write_x(item):
    i, ref = item
    ref.slab.arrays["x"][ref.slot] = i
    return ref


def test_aggregate_into_clean_path_and_partial_batch():
    arena = SlabArena({"x": ((), np.int64)}, batch_size=4, num_slabs=3)
    p = _slot_pipeline(arena, 10, _write_x)
    out = []
    with p.auto_stop():
        for b in p:
            slab = b.pop(SLAB_KEY)
            out.append(b["x"].copy())
            slab.release()
    assert [list(o) for o in out] == [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9]]
    assert arena.slabs_in_flight == 0  # everything recycled


def test_aggregate_into_compacts_holes_into_dense_batches():
    def flaky(item):
        i, ref = item
        if i % 4 == 1:  # 0..11 -> drop 1, 5, 9
            ref.mark_hole()
            raise ValueError(f"bad {i}")
        return _write_x(item)

    arena = SlabArena({"x": ((), np.int64)}, batch_size=4, num_slabs=3)
    p = _slot_pipeline(arena, 12, flaky, drop_last=True)
    out = []
    with p.auto_stop():
        for b in p:
            slab = b.pop(SLAB_KEY)
            out.append(list(b["x"]))
            slab.release()
    # 9 surviving items -> two dense batches of 4, tail dropped
    assert out == [[0, 2, 3, 4], [6, 7, 8, 10]]
    assert arena.slabs_in_flight == 0  # drained slabs auto-released


def test_aggregate_into_never_corrupts_under_out_of_order_upstream():
    """A completion-ordered stage between binder and aggregate violates the
    slot-order contract.  The stage must either fail loudly (monotonic-slot
    guard) or emit every row exactly once — never duplicate/lose rows."""
    import random

    def jitter_write(item):
        time.sleep(random.random() * 0.004)
        return _write_x(item)

    arena = SlabArena({"x": ((), np.int64)}, batch_size=4, num_slabs=4)
    p = (
        PipelineBuilder()
        .add_source(range(64))
        .pipe(arena.binder(), concurrency=1, name="slot")
        .pipe(jitter_write, concurrency=4, name="write", output_order="completion")
        .aggregate_into(arena, 4, name="batch")
        .add_sink(buffer_size=2)
        .build(num_threads=4)
    )
    got = []
    with p.auto_stop():
        try:
            for b in p:
                slab = b.pop(SLAB_KEY)
                got += list(b["x"])
                slab.release()
        except RuntimeError as e:
            assert "preserve input order" in str(e) or "pending rows" in str(e)
        else:
            assert sorted(got) == list(range(64))  # no row lost or duplicated


def test_aggregate_into_releases_tail_slab_spanning_partial_batch():
    """Regression: a final partial batch whose rows span two slabs fully
    drains the trailing (never-sealed) slab via compaction — it must still
    be released, not pinned forever."""

    def flaky(item):
        i, ref = item
        if i in (4, 5, 6):  # hole out most of slab 1
            ref.mark_hole()
            raise ValueError(f"bad {i}")
        return _write_x(item)

    arena = SlabArena({"x": ((), np.int64)}, batch_size=4, num_slabs=4)
    p = _slot_pipeline(arena, 10, flaky)  # drop_last=False
    out = []
    with p.auto_stop():
        for b in p:
            slab = b.pop(SLAB_KEY)
            out.append(list(b["x"]))
            slab.release()
    assert out == [[0, 1, 2, 3], [7, 8, 9]]
    assert arena.slabs_in_flight == 0


def test_image_loader_survives_read_failures(tmp_path):
    """Regression: a failing read must mark its pre-assigned slot as a hole,
    or the slab never fills and the loader stalls out of slabs."""
    ds = SyntheticImageDataset.materialize(tmp_path / "img", 64, hw=(8, 8), seed=0)

    class FlakyReads:
        def __len__(self):
            return len(ds)

        def read_bytes(self, i: int) -> bytes:
            if 16 <= i < 48:  # a failure burst spanning whole slabs
                raise OSError(f"transient I/O error on {i}")
            return ds.read_bytes(i)

    p = build_image_loader(FlakyReads(), batch_size=8, hw=(8, 8), num_threads=4)
    with p.auto_stop():
        batches = [np.asarray(b["images"]) for b in p]
    assert len(batches) == 4  # 32 surviving images -> 4 dense batches
    stats = {s.name: s for s in p.stats()}
    assert stats["read"].num_failed == 32


def test_arena_bounded_under_stalled_consumer_and_stats_exposed(tmp_path):
    """Acceptance: the arena never exceeds its ring under a stalled consumer,
    and Pipeline.stats() reports slabs_in_flight / bytes_allocated."""
    ds = SyntheticImageDataset.materialize(tmp_path / "img", 16, hw=(8, 8), seed=0)
    p = build_image_loader(ds, batch_size=4, hw=(8, 8), num_threads=4, epochs=None)
    p.start()
    try:
        time.sleep(0.02)
        ring = {s.name: s for s in p.stats()}["batch"].num_slabs
        assert ring >= 2
        for _ in range(40):  # sample while the pipeline fills up and stalls
            stats = {s.name: s for s in p.stats()}
            assert stats["batch"].slabs_in_flight <= ring
            time.sleep(0.01)
        stats = {s.name: s for s in p.stats()}
        assert stats["batch"].bytes_allocated == ring * 4 * 8 * 8 * 3
        assert stats["batch"].slabs_in_flight >= 1  # it is genuinely stalled
        assert "arena: slabs_in_flight=" in p.format_stats()
    finally:
        t0 = time.monotonic()
        p.stop()  # must not hang on a binder blocked in acquire
        assert time.monotonic() - t0 < 10


# ---------------------------------------------------------------------------
# loaders end-to-end: zero-copy path must be value-identical to list-collate
# ---------------------------------------------------------------------------
def test_image_loader_zero_copy_matches_fallback(tmp_path):
    ds = SyntheticImageDataset.materialize(tmp_path / "img", 24, hw=(32, 32), seed=0)
    got = {}
    for zc in (True, False):
        p = build_image_loader(ds, batch_size=8, hw=(16, 16), num_threads=4, zero_copy=zc)
        with p.auto_stop():
            got[zc] = [np.asarray(b["images"]).copy() for b in p]
    assert len(got[True]) == len(got[False]) == 3
    for a, b in zip(got[True], got[False]):
        np.testing.assert_array_equal(a, b)


def test_image_loader_zero_copy_native_size_decode(tmp_path):
    """stored hw == target hw routes through decode_into (no resize)."""
    ds = SyntheticImageDataset.materialize(tmp_path / "img", 8, hw=(16, 16), seed=3)
    p = build_image_loader(ds, batch_size=4, hw=(16, 16), num_threads=4)
    with p.auto_stop():
        batches = list(p)
    assert len(batches) == 2
    np.testing.assert_array_equal(np.asarray(batches[0]["images"])[0], ds[0])


def test_image_loader_falls_back_for_non_image_samples(tmp_path):
    """Regression: non-uint8/(H,W,3) datasets must not silently hole out
    every sample on the slab path — the loader sniffs one sample at build
    time and routes to list-collate."""
    import pathlib

    root = pathlib.Path(tmp_path / "clips")
    root.mkdir()
    rng = np.random.default_rng(0)
    names = []
    for i in range(8):  # 4-D "video" samples, like bench_video's
        clip = rng.integers(0, 256, (2, 16, 16, 3), dtype=np.uint8)
        name = f"{i:05d}.rpr"
        (root / name).write_bytes(encode_sample(clip))
        names.append(name)
    (root / "index.txt").write_text("\n".join(names))
    from repro.data import ArrayDataset

    p = build_image_loader(ArrayDataset(root), batch_size=4, hw=(8, 8), num_threads=4)
    with p.auto_stop():
        batches = list(p)
    assert len(batches) == 2  # all samples delivered, none holed out
    stats = {s.name: s for s in p.stats()}
    assert stats["decode"].num_failed == 0
    assert "collate" in stats  # it is the fallback pipeline


def test_lm_loader_zero_copy_matches_fallback():
    ds = SyntheticTokenDataset(200, vocab=1000, min_len=32, max_len=200, seed=1)
    got = {}
    for zc in (True, False):
        p, _ = build_lm_loader(
            ds, seq_len=64, batch_size=4, num_threads=4, seed=7, zero_copy=zc
        )
        with p.auto_stop():
            got[zc] = [
                {k: np.asarray(v).copy() for k, v in b.items()}
                for b, _ in zip(p, range(5))
            ]
    for a, b in zip(got[True], got[False]):
        for k in b:
            np.testing.assert_array_equal(a[k], b[k])


# ---------------------------------------------------------------------------
# device transfer: double buffering + uint8 wire format
# ---------------------------------------------------------------------------
def test_transfer_double_buffers_slab_release():
    arena = SlabArena({"x": ((4,), np.uint8)}, batch_size=2, num_slabs=3)
    tr = DeviceTransfer(hold_slabs=2)
    slabs = [arena.acquire() for _ in range(3)]
    for i, s in enumerate(slabs):
        s.arrays["x"][:] = i
        tr(s.as_batch())
    # the last hold_slabs=2 stay pinned; the oldest went back to the ring
    assert arena.slabs_in_flight == 2
    assert arena.try_acquire() is slabs[0]
    tr.flush()
    assert arena.slabs_in_flight == 1  # only our re-acquired slab remains


def test_transfer_hold_window_protects_delivered_batches():
    """The copy-then-free race, closed: recycling a slab must never corrupt
    a batch still inside the consumer window.  XLA's CPU backend ALIASES
    slab-sized host buffers in ``device_put`` (small probe arrays get
    copied — the decision is per-buffer), so this must use realistic slab
    sizes to bite."""
    tr = DeviceTransfer(consumer_window=0)  # hold = 2
    n = tr.hold_slabs
    assert n == 2
    row = 384 * 384 * 3  # the image loader's slab row: big enough to alias
    arena = SlabArena({"x": ((row,), np.uint8)}, batch_size=4, num_slabs=n + 1)
    outs = []
    for i in range(n + 1):
        s = arena.acquire()
        s.arrays["x"][:] = i
        outs.append(tr(s.as_batch()))
    # n+1 transfers -> exactly one slab (batch 0's) was recycled; scribble it
    s = arena.acquire()
    s.arrays["x"][:] = 255
    # every batch still inside the hold window must be intact
    for i in range(1, n + 1):
        assert (np.asarray(outs[i]["x"]) == i).all(), f"batch {i} corrupted"


def test_uint8_wire_downcasts_floats_4x_fewer_bytes():
    """Regression: the wire conversion used to be a no-op dict comprehension
    (`v if ... else v`), moving f32 images at full width."""
    rng = np.random.default_rng(0)
    imgs = rng.random((4, 8, 8, 3)).astype(np.float32)  # [0,1]-normalized
    scalars = np.arange(4, dtype=np.float32)  # non-image payload

    wire = DeviceTransfer(uint8_wire=True)
    full = DeviceTransfer(uint8_wire=False)
    out_w = wire({"images": imgs, "t": scalars})
    full({"images": imgs, "t": scalars})

    img_bytes = imgs.nbytes
    assert full.bytes_moved - wire.bytes_moved == img_bytes - img_bytes // 4
    assert full.bytes_moved - scalars.nbytes == 4 * (wire.bytes_moved - scalars.nbytes)
    assert np.asarray(out_w["images"]).dtype == np.uint8
    np.testing.assert_array_equal(
        np.asarray(out_w["images"]),
        np.clip(np.rint(imgs * 255.0), 0, 255).astype(np.uint8),
    )
    assert np.asarray(out_w["t"]).dtype == np.float32  # 1-D payload untouched


def test_uint8_wire_passes_uint8_through():
    imgs = np.arange(4 * 2 * 2 * 3, dtype=np.uint8).reshape(4, 2, 2, 3)
    tr = DeviceTransfer(uint8_wire=True)
    out = tr({"images": imgs})
    assert tr.bytes_moved == imgs.nbytes
    np.testing.assert_array_equal(np.asarray(out["images"]), imgs)
