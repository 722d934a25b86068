"""Behavioural tests for the SPDL pipeline engine (paper §5.5/§5.9)."""

import asyncio
import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeoutError

import pytest

from repro.core import OnError, PipelineBuilder, PipelineFailure


def build(src, *stages, sink=3, threads=4, **bkw):
    b = PipelineBuilder().add_source(src)
    for st in stages:
        st(b)
    return b.add_sink(buffer_size=sink).build(num_threads=threads, **bkw)


# ---------------------------------------------------------------------------
# basic semantics
# ---------------------------------------------------------------------------
def test_identity_map_preserves_order():
    p = build(range(100), lambda b: b.pipe(lambda x: x * 2, concurrency=4))
    with p.auto_stop():
        out = list(p)
    assert out == [x * 2 for x in range(100)]


def test_multi_stage_chain():
    p = build(
        range(50),
        lambda b: b.pipe(lambda x: x + 1, concurrency=3),
        lambda b: b.pipe(lambda x: x * 10, concurrency=2),
        lambda b: b.pipe(str),
    )
    with p.auto_stop():
        out = list(p)
    assert out == [str((x + 1) * 10) for x in range(50)]


def test_async_stage_function():
    async def slow_double(x):
        await asyncio.sleep(0.001)
        return x * 2

    p = build(range(40), lambda b: b.pipe(slow_double, concurrency=8))
    with p.auto_stop():
        assert list(p) == [x * 2 for x in range(40)]


def test_async_source():
    async def agen():
        for i in range(25):
            await asyncio.sleep(0)
            yield i

    p = build(agen(), lambda b: b.pipe(lambda x: -x))
    with p.auto_stop():
        assert list(p) == [-i for i in range(25)]


def test_aggregate_batches():
    p = build(range(10), lambda b: b.aggregate(3))
    with p.auto_stop():
        out = list(p)
    assert out == [[0, 1, 2], [3, 4, 5], [6, 7, 8], [9]]


def test_aggregate_drop_last():
    p = build(range(10), lambda b: b.aggregate(3, drop_last=True))
    with p.auto_stop():
        out = list(p)
    assert out == [[0, 1, 2], [3, 4, 5], [6, 7, 8]]


def test_disaggregate_roundtrip():
    p = build(range(20), lambda b: b.aggregate(6), lambda b: b.disaggregate())
    with p.auto_stop():
        assert list(p) == list(range(20))


def test_empty_source():
    p = build([], lambda b: b.pipe(lambda x: x))
    with p.auto_stop():
        assert list(p) == []


def test_completion_order_returns_all_items():
    import random

    def jitter(x):
        time.sleep(random.random() * 0.005)
        return x

    p = build(range(30), lambda b: b.pipe(jitter, concurrency=8, output_order="completion"))
    with p.auto_stop():
        out = list(p)
    assert sorted(out) == list(range(30))


# ---------------------------------------------------------------------------
# concurrency actually happens
# ---------------------------------------------------------------------------
def test_sync_stage_runs_concurrently_in_thread_pool():
    """time.sleep releases the GIL, so N concurrent tasks finish ~1 period."""
    n, dt = 8, 0.1

    def blocker(x):
        time.sleep(dt)
        return x

    p = build(range(n), lambda b: b.pipe(blocker, concurrency=n), threads=n, sink=n)
    t0 = time.monotonic()
    with p.auto_stop():
        out = list(p)
    elapsed = time.monotonic() - t0
    assert sorted(out) == list(range(n))
    assert elapsed < n * dt * 0.6, f"no concurrency: {elapsed:.2f}s for {n}x{dt}s tasks"


def test_stage_concurrency_is_bounded():
    active, peak = 0, 0
    lock = threading.Lock()

    def tracked(x):
        nonlocal active, peak
        with lock:
            active += 1
            peak = max(peak, active)
        time.sleep(0.01)
        with lock:
            active -= 1
        return x

    p = build(range(32), lambda b: b.pipe(tracked, concurrency=3), threads=16, sink=32)
    with p.auto_stop():
        list(p)
    assert peak <= 3, f"concurrency bound violated: peak={peak}"


def test_backpressure_blocks_upstream():
    """With a tiny sink and no consumer, the source must stall (bounded
    memory — the paper's queue-propagated congestion)."""
    produced = []

    def src():
        for i in range(10_000):
            produced.append(i)
            yield i

    p = build(src(), lambda b: b.pipe(lambda x: x, queue_size=2), sink=2)
    p.start()
    time.sleep(0.3)
    try:
        # source + in-flight + queues ≈ small constant, never thousands
        assert len(produced) < 50, f"backpressure failed: {len(produced)} produced"
    finally:
        p.stop()


def test_pipeline_processes_while_consumer_is_slow():
    """Prefetch: sink buffer should be (re)filled while consumer sleeps."""
    p = build(range(6), lambda b: b.pipe(lambda x: x), sink=3)
    with p.auto_stop():
        it = iter(p)
        first = next(it)
        time.sleep(0.2)  # let the pipeline run ahead
        assert p.sink_occupancy > 0.5
        rest = list(it)
    assert [first] + rest == list(range(6))


# ---------------------------------------------------------------------------
# robustness (paper §5.4)
# ---------------------------------------------------------------------------
def test_failures_are_skipped_and_counted():
    def flaky(x):
        if x % 3 == 0:
            raise ValueError(f"bad sample {x}")
        return x

    p = build(range(30), lambda b: b.pipe(flaky, concurrency=4, name="flaky"))
    with p.auto_stop():
        out = list(p)
    assert out == [x for x in range(30) if x % 3 != 0]
    stats = {s.name: s for s in p.stats()}
    assert stats["flaky"].num_failed == 10
    assert "bad sample" in stats["flaky"].last_error


def test_fail_fast_raises_in_consumer():
    def boom(x):
        if x == 5:
            raise RuntimeError("boom")
        return x

    p = build(range(100), lambda b: b.pipe(boom, on_error="fail", name="boom"))
    with p.auto_stop():
        with pytest.raises(PipelineFailure) as ei:
            list(p)
    assert ei.value.stage == "boom"
    assert isinstance(ei.value.__cause__, RuntimeError)


def test_timeout_is_a_skippable_failure():
    def hang(x):
        if x == 2:
            time.sleep(1.0)
        return x

    p = build(range(5), lambda b: b.pipe(hang, timeout=0.1, name="hang"))
    with p.auto_stop():
        out = list(p)
    assert out == [0, 1, 3, 4]
    assert {s.name: s for s in p.stats()}["hang"].num_failed == 1


def test_source_exception_fails_pipeline():
    def src():
        yield 1
        raise OSError("source died")

    p = build(src(), lambda b: b.pipe(lambda x: x))
    with p.auto_stop():
        with pytest.raises(OSError):
            _ = list(p)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------
def test_stop_is_idempotent_and_joins_threads():
    p = build(iter(int, 1), lambda b: b.pipe(lambda x: x))  # infinite source
    p.start()
    time.sleep(0.05)
    p.stop()
    p.stop()
    assert not p._thread.is_alive()


def test_auto_stop_cleans_up_on_consumer_exception():
    p = build(iter(int, 1), lambda b: b.pipe(lambda x: x))
    with pytest.raises(KeyboardInterrupt):
        with p.auto_stop():
            next(iter(p))
            raise KeyboardInterrupt
    assert not p._thread.is_alive()


def test_get_item_timeout():
    def hang(x):
        time.sleep(10)
        return x

    p = build(range(3), lambda b: b.pipe(hang))
    with p.auto_stop():
        with pytest.raises(FuturesTimeoutError):
            p.get_item(timeout=0.1)


def test_iterating_twice_resumes_where_left_off():
    p = build(range(10), lambda b: b.pipe(lambda x: x))
    with p.auto_stop():
        it = iter(p)
        first_three = [next(it) for _ in range(3)]
        rest = list(p)
    assert first_three == [0, 1, 2]
    assert rest == list(range(3, 10))


# ---------------------------------------------------------------------------
# visibility (paper §5.4)
# ---------------------------------------------------------------------------
def test_stats_identify_bottleneck_stage():
    def fast(x):
        return x

    def slow(x):
        time.sleep(0.01)
        return x

    p = build(
        range(40),
        lambda b: b.pipe(fast, name="fast"),
        lambda b: b.pipe(slow, name="slow"),
    )
    with p.auto_stop():
        list(p)
    stats = {s.name: s for s in p.stats()}
    # the fast stage is backpressured by the slow one
    assert stats["fast"].put_wait > stats["slow"].put_wait
    assert stats["slow"].avg_task_time > stats["fast"].avg_task_time
    # dashboard renders
    assert "slow" in p.format_stats()


def test_queue_depths_exposed():
    p = build(range(5), lambda b: b.pipe(lambda x: x))
    with p.auto_stop():
        list(p)
        depths = p.queue_depths()
    assert all(isinstance(v, tuple) for v in depths.values())


# ---------------------------------------------------------------------------
# builder validation
# ---------------------------------------------------------------------------
def test_builder_rejects_bad_usage():
    with pytest.raises(ValueError):
        PipelineBuilder().pipe(lambda x: x)
    with pytest.raises(TypeError):
        PipelineBuilder().add_source(42)
    with pytest.raises(ValueError):
        PipelineBuilder().add_source([1]).pipe(lambda x: x, concurrency=0)
    with pytest.raises(ValueError):
        PipelineBuilder().add_source([1]).pipe(lambda x: x, output_order="zigzag")
    with pytest.raises(ValueError):
        PipelineBuilder().add_source([1]).build()  # no processing stage


def test_ordered_pipe_bounds_results_ahead_of_stalled_emitter():
    """Backpressure invariant: with the emitter stalled (nobody drains the
    sink), completed results parked ahead of it must stay bounded by the
    stage's concurrency (+ the handful of already-emitted items sitting in
    the output/sink queues) — never the whole source."""
    conc, queue_size, sink = 4, 1, 1
    completed = []
    lock = threading.Lock()

    def work(x):
        with lock:
            completed.append(x)
        return x

    p = build(
        range(10_000),
        lambda b: b.pipe(work, concurrency=conc, queue_size=queue_size),
        sink=sink,
    )
    p.start()
    time.sleep(0.4)
    try:
        # parked-in-task_q (<= conc) + emitter-held (1) + out_q + sink
        bound = conc + 1 + queue_size + sink
        assert len(completed) <= bound, f"unbounded run-ahead: {len(completed)}"
        assert completed, "pipeline made no progress at all"
    finally:
        p.stop()


def test_ordered_pipe_fail_fast_with_full_task_queue_no_deadlock():
    """A failing item under OnError.FAIL must tear the stage down even when
    the reader is parked on a full task_q (stalled consumer)."""

    def boom(x):
        if x == 3:
            raise RuntimeError("boom")
        time.sleep(0.01)  # keep the task_q populated behind the failure
        return x

    p = build(
        range(10_000),
        lambda b: b.pipe(boom, concurrency=2, on_error="fail", queue_size=1, name="boom"),
        sink=1,
    )
    with p.auto_stop():
        with pytest.raises(PipelineFailure) as ei:
            while True:  # bounded waits: a deadlock fails the test, not CI
                p.get_item(timeout=10)
    assert ei.value.stage == "boom"


def test_fail_fast_completion_order_infinite_source():
    """Regression (TaskGroup fail-fast): a child failure must
    interrupt a stage body that is still awaiting input — with an infinite
    source the error would otherwise never surface and teardown would hang."""

    import itertools

    def boom(x):
        if x == 5:
            raise RuntimeError("boom")
        return x

    p = build(
        itertools.count(),
        lambda b: b.pipe(
            boom, concurrency=2, output_order="completion", on_error="fail", name="boom"
        ),
    )
    with p.auto_stop():
        with pytest.raises(PipelineFailure) as ei:
            while True:
                p.get_item(timeout=15)
    assert ei.value.stage == "boom"


def test_process_pool_stage():
    """§5.8: GIL-holding stages can run in a process pool."""
    from concurrent.futures import ProcessPoolExecutor

    with ProcessPoolExecutor(max_workers=2) as ex:
        p = build(range(20), lambda b: b.pipe(_square, concurrency=2, executor=ex))
        with p.auto_stop():
            out = list(p)
    assert out == [x * x for x in range(20)]


def _square(x):  # module-level: must be picklable for the process pool
    return x * x
