"""Program spans on the profiler's clock (core/trace.py, data/transfer.py).

Covers the clock anchor (a span mapped through it lands where the
profiler saw the same call), the ``h2d`` span from ``device_put`` to
residency and its watcher thread, the ``h2d_unresident_releases``
counter, and the ``compile`` spans of the ``jax.monitoring`` listener —
and that none of it exists while tracing is off.
"""

import glob
import os
import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax", reason="the profiler clock needs jax")
import jax.numpy as jnp  # noqa: E402
from jax._src import monitoring as _monitoring  # noqa: E402

from repro.core import NULL_TRACER, Tracer, set_tracer, tracing  # noqa: E402
from repro.core import trace as trace_mod  # noqa: E402
from repro.core.metrics import stage_metrics_lines  # noqa: E402
from repro.core.stats import format_stats  # noqa: E402
from repro.data import transfer as transfer_mod  # noqa: E402
from repro.data.arena import SlabArena  # noqa: E402
from repro.data.transfer import DeviceTransfer  # noqa: E402

WATCHER = "h2d-watcher"


def _watchers() -> int:
    return sum(t.name == WATCHER for t in threading.enumerate())


def _compile_listeners() -> int:
    return sum(
        isinstance(cb, trace_mod._CompileListener)
        for cb in _monitoring.get_event_duration_listeners()
    )


def _spans(tracer, name):
    return [e for e in tracer.events() if e["ph"] == "X" and e["name"] == name]


def _wait_for(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.001)


def _slab_batches(arena, n):
    out = []
    for i in range(n):
        s = arena.acquire()
        s.arrays["x"][:] = i
        out.append(s.as_batch())
    return out


# -- clock anchor -----------------------------------------------------------
def test_anchor_maps_spans_onto_the_profiler_clock(tmp_path):
    """Spans mapped through the anchor land where the profiler saw the
    annotation wrapping the same call: the median error over five calls,
    so that a thread switch inside one call is not read as clock error."""
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.anchor()
        for _ in range(5):
            with jax.profiler.TraceAnnotation("test.work"):
                with tr.span("work", "test"):
                    time.sleep(0.002)
        tr.anchor()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    events = [
        e for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
    ]
    anchors = sorted(e.start_ns for e in events if e.name == trace_mod.ANCHOR)
    work = sorted((e.start_ns, e.start_ns + e.duration_ns)
                  for e in events if e.name == "test.work")
    offsets = tr.clock_offsets(anchors)
    assert len(offsets) == 2
    assert abs(offsets[1] - offsets[0]) < 50e3  # drift between anchors, ns
    spans = sorted((e["ts"] * 1e3, (e["ts"] + e["dur"]) * 1e3)
                   for e in tr.events(offset_ns=offsets[0]) if e["name"] == "work")
    assert len(spans) == len(work) == 5
    err = np.abs(np.array(spans) - np.array(work))
    assert np.median(err[:, 0]) < 50e3
    assert np.median(err[:, 1]) < 50e3


def test_clock_offsets_pair_the_ith_anchors():
    tr = Tracer()
    a = [tr.anchor() for _ in range(3)]
    starts = [1_000.0, 5_000.0, 9_000.0]
    assert tr.clock_offsets(starts) == pytest.approx(
        [s - t * 1e9 for s, t in zip(starts, a)]
    )
    # a profile that caught only the first anchors pairs only those
    assert len(tr.clock_offsets(starts[:2])) == 2
    # on a profile's clock every event moves by the same offset
    t0 = time.monotonic()
    tr.complete("x", "c", t0, 0.002)
    (x,) = [e for e in tr.events(offset_ns=-t0 * 1e9 + 7e6) if e["name"] == "x"]
    assert x["ts"] == pytest.approx(7e3)  # µs
    assert x["dur"] == pytest.approx(2e3)
    assert trace_mod.on_profiler_clock(t0, 7e6) == pytest.approx(t0 * 1e9 + 7e6)
    doc = tr.to_chrome(offset_ns=-t0 * 1e9)
    assert any(e.get("name") == "x" and e["ts"] == pytest.approx(0.0, abs=1e-3)
               for e in doc["traceEvents"])


# -- h2d span and its watcher -----------------------------------------------
def test_traced_transfer_records_one_h2d_span_per_batch():
    tr = Tracer()
    transfer = DeviceTransfer(tracer=tr)
    batches = [{"images": np.full((4, 8, 8, 3), i, np.uint8)} for i in range(5)]
    before = set(threading.enumerate())
    outs = [transfer(b) for b in batches]
    (thread,) = [t for t in set(threading.enumerate()) - before if t.name == WATCHER]
    jax.block_until_ready(outs)
    transfer.flush()
    thread.join(timeout=10)
    assert not thread.is_alive()  # flush stops the watcher
    spans = _spans(tr, "h2d")
    assert [e["args"]["batch"] for e in spans] == [1, 2, 3, 4, 5]
    for e in spans:
        assert e["cat"] == "transfer"
        assert e["args"]["bytes"] == 4 * 8 * 8 * 3
        assert e["dur"] >= 0.0


def test_untraced_transfer_starts_no_thread_and_no_listener():
    assert _compile_listeners() == 0
    before = _watchers()
    for tracer in (None, NULL_TRACER):
        transfer = DeviceTransfer(tracer=tracer)
        jax.block_until_ready([transfer({"x": np.ones((4, 4), np.float32)}) for _ in range(3)])
        assert _watchers() == before
        assert transfer.stats()["h2d_unresident_releases"] == 0
        transfer.flush()
    set_tracer(None)
    assert _compile_listeners() == 0


def test_held_back_watcher_counts_unresident_releases(monkeypatch):
    arena = SlabArena({"x": ((4,), np.uint8)}, batch_size=2, num_slabs=5)
    tr = Tracer()
    transfer = DeviceTransfer(hold_slabs=2, tracer=tr)
    gate = threading.Event()
    real = jax.block_until_ready

    def held_back(x):
        assert gate.wait(timeout=30)
        return real(x)

    monkeypatch.setattr(transfer_mod.jax, "block_until_ready", held_back)
    for b in _slab_batches(arena, 5):
        transfer(b)
    # batches 1..3 left the hold ring while the watcher saw none resident
    assert transfer.h2d_unresident_releases == 3
    assert transfer.stats()["h2d_unresident_releases"] == 3
    gate.set()
    transfer.flush()
    assert transfer.h2d_unresident_releases == 3  # flush waits, then releases
    assert arena.slabs_in_flight == 0
    assert len(_spans(tr, "h2d")) == 5


def test_unresident_releases_stay_zero_when_copies_keep_up():
    arena = SlabArena({"x": ((4,), np.uint8)}, batch_size=2, num_slabs=4)
    tr = Tracer()
    transfer = DeviceTransfer(hold_slabs=2, tracer=tr)
    for k in range(1, 7):
        # a consumer slower than the copy: each batch is resident before
        # the next is put, as the hold ring assumes
        (b,) = _slab_batches(arena, 1)
        jax.block_until_ready(transfer(b))
        _wait_for(lambda: len(_spans(tr, "h2d")) == k)
    transfer.flush()
    assert transfer.h2d_unresident_releases == 0
    assert arena.slabs_in_flight == 0


def test_unresident_releases_reach_dashboards():
    from repro.core.stats import StageStatsSnapshot

    row = StageStatsSnapshot(
        name="transfer", concurrency=1, num_in=4, num_out=4, num_failed=0,
        qps=0.0, avg_task_time=0.0, occupancy=0.0, get_wait=0.0, put_wait=0.0,
        last_error=None,
        h2d_unresident_releases=2,
    )
    assert "[transfer] h2d: unresident_releases=2" in format_stats([row])
    lines = "\n".join(stage_metrics_lines([row]))
    assert "repro_h2d_unresident_releases_total" in lines
    quiet = StageStatsSnapshot(
        name="transfer", concurrency=1, num_in=4, num_out=4, num_failed=0,
        qps=0.0, avg_task_time=0.0, occupancy=0.0, get_wait=0.0, put_wait=0.0,
        last_error=None,
    )
    assert "unresident" not in format_stats([quiet])
    assert "unresident" not in "\n".join(stage_metrics_lines([quiet]))


def test_traced_image_loader_records_h2d_and_stops_its_watcher(tmp_path):
    from repro.data import SyntheticImageDataset, build_image_loader

    ds = SyntheticImageDataset.materialize(tmp_path, 32, hw=(16, 16), seed=5)
    tr = Tracer()
    pipe = build_image_loader(ds, batch_size=4, hw=(16, 16), epochs=1, trace=tr)
    with pipe.auto_stop():
        got = [jax.block_until_ready(b) for b in pipe]
        row = next(s for s in pipe.stats() if s.name == "transfer")
    assert len(got) == 8
    spans = _spans(tr, "h2d")
    assert sorted(e["args"]["batch"] for e in spans) == list(range(1, 9))
    assert all(e["args"]["bytes"] == 4 * 16 * 16 * 3 for e in spans)
    assert row.h2d_unresident_releases >= 0
    _wait_for(lambda: _watchers() == 0)


# -- compile spans ------------------------------------------------------------
def test_compile_listener_records_new_compiles_while_installed():
    x = jnp.arange(8.0)
    with tracing() as tr:
        assert _compile_listeners() == 1
        jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    assert _compile_listeners() == 0
    spans = _spans(tr, "compile")
    assert len(spans) == 1
    (span,) = spans
    assert span["cat"] == "compile"
    assert span["args"]["cached"] is False
    assert "lambda" in span["args"]["fun"]
    assert span["dur"] > 0.0
    jax.jit(lambda v: v * 5.0 - 2.0)(x).block_until_ready()
    assert len(_spans(tr, "compile")) == 1  # uninstalled: nothing new


def test_compile_listener_marks_persistent_cache_hits():
    with tracing() as tr:
        jax.monitoring.record_event_duration_secs(trace_mod.CACHE_RETRIEVAL_EVENT, 0.01)
        jax.monitoring.record_event_duration_secs(
            trace_mod.BACKEND_COMPILE_EVENT, 0.02, fun_name="hit")
        jax.monitoring.record_event_duration_secs(
            trace_mod.BACKEND_COMPILE_EVENT, 0.5, fun_name="miss")
        jax.monitoring.record_event_duration_secs("/jax/other", 1.0)
    spans = {e["args"]["fun"]: e for e in _spans(tr, "compile")}
    assert set(spans) == {"hit", "miss"}
    assert spans["hit"]["args"]["cached"] is True
    assert spans["miss"]["args"]["cached"] is False
    assert spans["miss"]["dur"] == pytest.approx(0.5e6)
    # the span ends when the event fires
    assert spans["miss"]["ts"] + spans["miss"]["dur"] >= spans["hit"]["ts"] + spans["hit"]["dur"]
