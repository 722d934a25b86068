"""Flight recorder + time-series telemetry (core/trace.py, core/metrics.py).

Covers the tracer's ring/export contract, the module-global install, the
engine integration (a traced pipeline run yields stage + queue spans), the
StatsHistory window/staleness math, and the Prometheus export surface —
standalone server and the mounts on both shard HTTP servers.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from repro.core import (
    NULL_TRACER,
    MetricsExporter,
    PipelineBuilder,
    StatsHistory,
    Tracer,
    get_tracer,
    set_tracer,
    tracing,
)
from repro.core.metrics import CONTENT_TYPE_LATEST, stage_metrics_lines
from repro.core.stats import StageStatsSnapshot


def snap(name="s", **kw) -> StageStatsSnapshot:
    base = dict(
        name=name, concurrency=2, num_in=0, num_out=0, num_failed=0,
        qps=0.0, avg_task_time=0.0, occupancy=0.0, get_wait=0.0,
        put_wait=0.0, last_error=None,
    )
    base.update(kw)
    return StageStatsSnapshot(**base)


def _get(url: str) -> tuple[int, str, str]:
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, resp.headers.get("Content-Type", ""), resp.read().decode()


# -- Tracer ----------------------------------------------------------------
def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("x", "cat"):
        pass
    NULL_TRACER.complete("x", "cat", 0.0, 1.0)
    NULL_TRACER.instant("x")
    assert NULL_TRACER.events() == []


def test_tracer_records_all_phases():
    tr = Tracer()
    t0 = time.monotonic()
    tr.complete("work", "stage", t0, 0.5, {"items": 3})
    tr.instant("mark", "straggler")
    with tr.span("fetch", "shard"):
        pass
    evs = tr.events()
    assert [e["ph"] for e in evs] == ["X", "i", "X"]
    x = evs[0]
    assert x["name"] == "work" and x["cat"] == "stage"
    assert x["dur"] == pytest.approx(0.5e6)
    assert x["args"] == {"items": 3}
    assert evs[1]["s"] == "t"  # thread-scoped instant
    assert len(tr) == 3


def test_tracer_events_sorted_and_epoch_relative():
    tr = Tracer()
    now = time.monotonic()
    tr.complete("late", "c", now + 2.0, 0.1)
    tr.complete("early", "c", now + 1.0, 0.1)
    evs = tr.events()
    assert [e["name"] for e in evs] == ["early", "late"]
    assert all(e["ts"] >= 0 for e in evs)


def test_tracer_ring_is_bounded():
    tr = Tracer(capacity_per_thread=16)
    for i in range(100):
        tr.instant(f"e{i}")
    assert len(tr) == 16
    assert tr.events()[-1]["name"] == "e99"  # newest survive


def test_tracer_capacity_validation():
    with pytest.raises(ValueError):
        Tracer(capacity_per_thread=0)


def test_tracer_one_track_per_thread():
    tr = Tracer()
    tr.instant("main")

    def worker():
        tr.instant("from-worker")

    t = threading.Thread(target=worker, name="trace-worker")
    t.start()
    t.join()
    assert len({e["tid"] for e in tr.events()}) == 2
    names = {
        m["args"]["name"]
        for m in tr.to_chrome()["traceEvents"]
        if m["ph"] == "M" and m["name"] == "thread_name"
    }
    assert "trace-worker" in names


def test_tracer_clear():
    tr = Tracer()
    tr.instant("x")
    tr.clear()
    assert len(tr) == 0 and tr.events() == []


def test_chrome_export_round_trip(tmp_path):
    tr = Tracer()
    tr.complete("work", "stage", time.monotonic(), 0.01,
                {"obj": object()})  # non-JSON arg must not break export
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.loads((tmp_path / "trace.json").read_text())
    assert path.endswith("trace.json")
    assert doc["displayTimeUnit"] == "ms"
    phs = [e["ph"] for e in doc["traceEvents"]]
    assert "M" in phs and "X" in phs
    proc = [e for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"]
    assert proc[0]["args"]["name"] == "repro-pipeline"


def test_tracing_context_installs_and_restores():
    assert get_tracer() is NULL_TRACER
    with tracing() as tr:
        assert get_tracer() is tr and tr.enabled
        with tracing() as inner:
            assert get_tracer() is inner
        assert get_tracer() is tr
    assert get_tracer() is NULL_TRACER
    prev = set_tracer(None)
    assert prev is NULL_TRACER


# -- engine integration ----------------------------------------------------
def test_traced_pipeline_emits_stage_and_queue_spans():
    tr = Tracer()
    p = (
        PipelineBuilder()
        .add_source(range(64))
        .pipe(lambda x: x + 1, concurrency=2, chunk=8, name="inc")
        .aggregate(16, name="agg")
        .add_sink(buffer_size=2)
        .build(num_threads=4, trace=tr)
    )
    with p.auto_stop():
        out = [x for b in p for x in b]
    assert out == [x + 1 for x in range(64)]
    cats = {e["cat"] for e in tr.events()}
    assert "stage" in cats and "queue" in cats
    stage_spans = [e for e in tr.events() if e["cat"] == "stage"]
    assert any(e["name"] == "inc" for e in stage_spans)
    assert all(e["dur"] >= 0 for e in stage_spans)


def test_untraced_pipeline_records_nothing():
    p = (
        PipelineBuilder()
        .add_source(range(8))
        .pipe(lambda x: x, name="id")
        .add_sink(buffer_size=2)
        .build(num_threads=2)
    )
    with p.auto_stop():
        list(p)
    assert len(get_tracer().events()) == 0  # NULL tracer throughout


# -- StatsHistory ----------------------------------------------------------
class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def rows_fn(counts):
    """stats_fn producing one row whose counters follow `counts` (mutable)."""

    def fn():
        return [
            snap(
                num_out=counts["out"], num_in=counts["out"],
                task_time=counts["task"], get_wait=counts.get("get", 0.0),
                put_wait=counts.get("put", 0.0),
            )
        ]

    return fn


def test_history_requires_source_and_capacity():
    with pytest.raises(ValueError):
        StatsHistory()
    with pytest.raises(ValueError):
        StatsHistory(stats_fn=lambda: [], capacity=1)


def test_history_window_rates():
    clock = FakeClock()
    counts = {"out": 0, "task": 0.0}
    h = StatsHistory(stats_fn=rows_fn(counts), clock=clock)
    h.sample()
    clock.t += 10.0
    counts.update(out=50, task=5.0, get=2.0, put=1.0)
    h.sample()
    w = h.window()["s"]
    assert w.qps == pytest.approx(5.0)
    assert w.in_rate == pytest.approx(5.0)
    assert w.dt == pytest.approx(10.0)
    assert w.occupancy == pytest.approx(5.0 / (10.0 * 2))  # conc=2
    assert w.get_wait_frac == pytest.approx(0.2)
    assert w.put_wait_frac == pytest.approx(0.1)


def test_history_window_needs_two_samples():
    h = StatsHistory(stats_fn=rows_fn({"out": 0, "task": 0.0}))
    assert h.window() == {}
    assert h.last() is None


def test_history_window_picks_deep_enough_baseline():
    clock = FakeClock()
    counts = {"out": 0, "task": 0.0}
    h = StatsHistory(stats_fn=rows_fn(counts), clock=clock)
    for out in (0, 10, 20, 30):
        counts["out"] = out
        h.sample()
        clock.t += 1.0
    clock.t -= 1.0  # the last sample's timestamp
    # ask for 2s: baseline must be the newest sample >= 2s old (t=100+1),
    # giving dt=2 and a delta of 20 items -> 10/s
    w = h.window(2.0)["s"]
    assert w.dt == pytest.approx(2.0)
    assert w.qps == pytest.approx(10.0)
    # deeper than history: falls back to the oldest sample
    w = h.window(100.0)["s"]
    assert w.dt == pytest.approx(3.0)


def test_history_quiet_for_tracks_progress():
    clock = FakeClock()
    counts = {"out": 0, "task": 0.0}
    h = StatsHistory(stats_fn=rows_fn(counts), clock=clock)
    h.sample()
    clock.t += 5.0
    h.sample()  # no progress: quiet grows
    assert h.quiet_for(0) == pytest.approx(5.0)
    assert h.quiet_for(-1) == pytest.approx(5.0)  # pipeline sentinel
    counts["out"] = 3
    clock.t += 1.0
    h.sample()
    assert h.quiet_for(0) == 0.0
    assert h.quiet_for(99) == 0.0  # unknown row: never reported stalled


def test_history_ring_bounded_and_background():
    h = StatsHistory(stats_fn=rows_fn({"out": 0, "task": 0.0}), capacity=4)
    for _ in range(10):
        h.sample()
    assert len(h) == 4
    with StatsHistory(stats_fn=rows_fn({"out": 0, "task": 0.0})) as bg:
        bg._stop_evt.wait(0.05)
    bg.stop()  # idempotent


# -- Prometheus export -----------------------------------------------------
def test_stage_metrics_lines_families_and_labels():
    s = snap(num_out=5, errors_by_type=(("ValueError", 2),),
             time_to_first_s=0.5, cache_hits=3, cache_misses=1,
             peer_hits=2, peer_bytes=10, origin_bytes=20,
             num_slabs=2, slabs_in_flight=1, stragglers=1)
    text = "\n".join(stage_metrics_lines([s], pipeline="train"))
    assert '# TYPE repro_stage_items_out_total counter' in text
    assert 'repro_stage_items_out_total{pipeline="train",stage="s"} 5' in text
    assert 'repro_stage_errors_total{type="ValueError",pipeline="train",stage="s"} 2' in text
    assert "repro_stage_time_to_first_item_seconds" in text
    assert "repro_shard_cache_hits_total" in text
    assert "repro_shard_peer_hits_total" in text
    assert "repro_arena_slabs_in_flight" in text
    assert "repro_stage_stragglers_total" in text
    # HELP/TYPE rendered once per family even with many rows
    two = "\n".join(stage_metrics_lines([s, snap(name="t")]))
    assert two.count("# TYPE repro_stage_items_out_total counter") == 1


def test_metrics_exporter_render_and_errors():
    exp = MetricsExporter()
    exp.add_collector(lambda: ["a_metric 1"])

    def bad():
        raise RuntimeError("scrape-time failure")

    exp.add_collector(bad)
    text = exp.render()
    assert "a_metric 1" in text
    assert "# collector error:" in text and "scrape-time failure" in text


class FakeSampler:
    def current(self):
        return 2.5, 1 << 30


def test_metrics_server_scrape():
    exp = MetricsExporter()
    exp.add_resource_sampler(FakeSampler())
    with exp.serve() as server:
        status, ctype, body = _get(server.url)
        assert status == 200 and ctype == CONTENT_TYPE_LATEST
        assert "repro_process_cpu_seconds_total 2.5" in body
        assert f"repro_process_rss_bytes {1 << 30}" in body
        with pytest.raises(urllib.error.HTTPError):
            _get(server.url.replace("/metrics", "/other"))


def test_metrics_exporter_add_pipeline_samples_history():
    counts = {"out": 0, "task": 0.0}

    class FakePipe:
        def stats(self):
            return rows_fn(counts)()

    pipe = FakePipe()
    h = StatsHistory(pipeline=pipe)
    exp = MetricsExporter()
    exp.add_pipeline(pipe, name="train", history=h)
    exp.render()
    counts["out"] = 4
    text = exp.render()  # each scrape appends a sample -> window gauges
    assert len(h) == 2
    assert 'repro_stage_window_qps{pipeline="train",stage="s"}' in text
    assert 'repro_stage_items_out_total{pipeline="train",stage="s"} 4' in text


def test_shard_server_metrics_mount(tmp_path):
    from repro.data.shards.testing import serve_shards

    (tmp_path / "x.bin").write_bytes(b"payload")
    exp = MetricsExporter()
    exp.add_collector(lambda: ["mounted_metric 42"])
    with serve_shards(tmp_path, metrics=exp) as srv:
        before = srv.requests
        status, ctype, body = _get(srv.url + "/metrics")
        assert status == 200 and ctype == CONTENT_TYPE_LATEST
        assert "mounted_metric 42" in body
        assert srv.requests == before  # scrapes bypass the chaos counters
        # shard serving still works on the same port
        status, _, body = _get(srv.url + "/x.bin")
        assert status == 200 and body == "payload"


def test_shard_server_metrics_unmounted_404(tmp_path):
    from repro.data.shards.testing import serve_shards

    with serve_shards(tmp_path) as srv:
        with pytest.raises(urllib.error.HTTPError):
            _get(srv.url + "/metrics")


def test_peer_server_metrics_mount():
    from repro.data import PeerShardServer

    exp = MetricsExporter()
    exp.add_collector(lambda: ["peer_metric 7"])
    server = PeerShardServer(object(), metrics=exp).start()
    try:
        status, ctype, body = _get(server.url + "/metrics")
        assert status == 200 and ctype == CONTENT_TYPE_LATEST
        assert "peer_metric 7" in body
    finally:
        server.close()
