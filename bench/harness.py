"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one cell is found by name: the cell in
``BENCHMARK.json`` names its configuration (``bench/configs/<name>.json``)
and traffic (``bench/traffic/<name>.json``); the configuration names its
consumer (``bench/consumers/<name>.py``), its feed
(``bench/feeds/<name>.py``, ``image_files`` where it names none) and,
where it has one, its plain reference beside it; each metric is read by
``bench/metrics/<name>.py``.

A feed owns what is particular to the records and their storage:

- ``fields``: the batch keys the consumer gets; with one key the consumer
  gets that array, with several a dict of them;
- ``write(workdir, config, traffic, n, seed, threads)`` lays the corpus
  out from the seed;
- ``build(workdir, config, traffic, *, batch, seed, shardings, sampler)``
  returns the pipeline as a user states it (``get_item``, ``stats``,
  ``queue_depths``, ``auto_stop``);
- ``reference_batches(config, traffic, *, n, batch, seed, shuffle)`` returns
  ``batch_at(k, dtype=np.float32)``, the plain reference of the stream's
  ``k``-th batch in the consumer's form;
- ``check(sample, batch_at, calibrate)`` returns the numbers compared with
  the configuration's ``limits`` and, with ``calibrate``, the control's.

Set-up: JAX and the chips, the corpus written from the seed into a
temporary directory, the consumer's state made on the device, the feed's
pipeline built, the consumer compiled on the first batch and driven
through its first steps, then the sink left to fill.

The window: at most two steps in flight (after dispatching step ``k`` the
loop blocks on step ``k - 1``), batches taken from the loader one at a
time, until ``seconds`` have passed and the last
dispatched step has completed.  With ``trace`` the profiler records the
last ``SETTLE_S + TRACE_S`` seconds of it, and the reduction reads the
last ``TRACE_S``: starting the profiler stalls host-to-device copies.

The check, once the window has closed and the device's peak memory has
been read: a seeded sample of the window's batches against the feed's
reference, each chip's rows of every field against its place, the
samples the loader failed, and whatever the consumer compares.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from collections import deque

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
TRACE_S = 4.0  # seconds of the window a traced run reads from the profile
SETTLE_S = 3.0  # the profiler stalls transfers as it starts: record, but skip, this much
SINK_FILL_S = 5.0  # longest wait for the sink to fill before the window
DEFAULT_FEED = "image_files"


class NoChip(RuntimeError):
    """JAX finds no accelerator of the kind asked for, or too few."""


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _covers(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entries and files, found by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; it has {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    feed = root / "bench" / "feeds" / f"{config.get('feed', DEFAULT_FEED)}.py"
    e2e = [m for m in spec["end_to_end"] if _covers(m, workload)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _covers(m, workload) and m["moves"] in moved]
    return {
        "cell": cell, "config": config, "config_dir": (root / configs[cell["config"]]["file"]).parent,
        "traffic": traffic, "feed": feed, "end_to_end": e2e, "per_layer": layer,
    }


def cpu_seconds() -> float:
    """utime + stime of every thread of this process."""
    with open("/proc/self/stat") as f:
        parts = f.read().rsplit(")", 1)[1].split()
    return (int(parts[11]) + int(parts[12])) / os.sysconf("SC_CLK_TCK")


def device_peak_bytes(stats: dict) -> int:
    """A chip's peak memory: its buffers' peak plus the peak the runtime
    reserved for compiled programs' temporaries, which TPUs hold apart
    from ``peak_bytes_in_use``."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def chips_for(n: int, platform: str):
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        raise NoChip(f"JAX finds platform {devices[0].platform!r}, the benchmark runs on {platform!r}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devices)}")
    return devices[:n]


def stage_rows(pipe) -> dict:
    return {s.name: s for s in pipe.stats()}


def stage_delta(before: dict, after: dict) -> dict:
    return {
        name: {
            "task_time": a.task_time - before[name].task_time,
            "num_out": a.num_out - before[name].num_out,
            "num_failed": a.num_failed - before[name].num_failed,
            "concurrency": a.concurrency,
            "bytes_allocated": a.bytes_allocated,
        }
        for name, a in after.items()
    }


class Feed:
    """The consumer's side of the loader: batches in stream order, and the
    stream index of each.  A batch is the pipeline's item cut to
    ``fields``: that field's array where there is one, else a dict.

    The loader hands a batch's host memory back to its ring a fixed number
    of batches after the batch's copy to the device is issued, not once
    the copy is done.  So batches are taken one at a time, and the batch
    in hand must be on the device before the next is taken; otherwise a
    copy that the runtime holds back (as the profiler's start does) reads
    memory already refilled with later records."""

    def __init__(self, pipe, fields: tuple[str, ...]):
        self.pipe, self.fields = pipe, tuple(fields)
        self.stash, self.taken, self.last = deque(), 0, None

    def pick(self, item):
        if len(self.fields) == 1:
            return item[self.fields[0]]
        return {f: item[f] for f in self.fields}

    def next(self):
        import jax

        if self.last is not None:
            with jax.profiler.TraceAnnotation("bench.batch_ready"):
                jax.block_until_ready(self.last)
        with jax.profiler.TraceAnnotation("bench.get_batch"):
            if not self.stash:
                self.stash.append(self.pick(self.pipe.get_item()))
            self.taken += 1
            self.last = self.stash.popleft()
            return self.last

    def peek(self):
        x = self.next()
        self.taken -= 1
        self.stash.appendleft(x)
        return x


class Reservoir:
    """A seeded uniform sample of ``k`` of the window's batches."""

    def __init__(self, k: int, seed: int):
        self.k, self.seen, self.items = k, 0, []
        self.rng = np.random.default_rng([seed, 5])

    def offer(self, index: int, x) -> None:
        if len(self.items) < self.k:
            self.items.append((index, x))
        else:
            r = int(self.rng.integers(0, self.seen + 1))
            if r < self.k:
                self.items[r] = (index, x)
        self.seen += 1


def misplaced_rows(batch, devices) -> int:
    """Rows of any field that are not on the chip that owns them (chip
    ``i`` of ``n`` owns the ``i``-th block of rows)."""
    import jax

    return max(_misplaced(x, devices) for x in jax.tree_util.tree_leaves(batch))


def _misplaced(x, devices) -> int:
    rows = x.shape[0] // len(devices)
    bad, starts = 0, set()
    for s in x.addressable_shards:
        start = s.index[0].start or 0
        starts.add(start)
        if start % rows or s.data.shape[0] != rows or s.device != devices[start // rows]:
            bad += s.data.shape[0]
    return bad + rows * (len(devices) - len(starts))


@dataclasses.dataclass
class CheckContext:
    """What a consumer's check may use: the seed, the mesh, the feed's
    reference of any batch of the stream, and the configuration's
    reference."""

    seed: int
    mesh: object
    calibrate: bool
    reference_batch: object
    reference_module: object


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, *, t0: float,
    platform: str = "tpu", root: pathlib.Path = ROOT, calibrate: bool = False,
) -> dict:
    """One run; returns the result line's object (with ``readings`` added
    under ``calibrate``).  Raises ``NoChip`` before any work off the chip."""
    spec = load_cell(workload, root)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    chips = chips_for(int(cell["chips"]), platform)
    from repro.compile_cache import use_compile_cache
    from repro.data.sampler import CheckpointableSampler

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    mesh = Mesh(np.array(chips), ("data",))
    rows = NamedSharding(mesh, P("data"))
    batch = int(config["batch_per_chip"]) * len(chips)
    n = int(config["num_records"])
    if n % batch:
        raise ValueError(f"num_records {n} is not a whole number of batches of {batch}")
    shuffle = bool(traffic["sampler"]["shuffle"])
    bench_dir = root / "bench"
    consumer_mod = load_module(bench_dir / "consumers" / f"{config['consumer']}.py",
                               f"bench_consumer_{config['consumer']}")
    feed_mod = load_module(spec["feed"], f"bench_feed_{spec['feed'].stem}")
    workdir = tempfile.mkdtemp(prefix="bench-corpus-")
    try:
        parts = {"start_s": time.monotonic() - t0}
        feed_mod.write(workdir, config, traffic, n, seed, threads=min(16, os.cpu_count() or 4))
        parts["corpus_s"] = time.monotonic() - t0 - sum(parts.values())
        consumer = consumer_mod.Consumer(config, mesh, seed, batch)
        pipe = feed_mod.build(
            workdir, config, traffic, batch=batch, seed=seed, shardings=rows,
            sampler=CheckpointableSampler(n, batch_size=batch, seed=seed, shuffle=shuffle),
        )
        with pipe.auto_stop():
            feed = Feed(pipe, feed_mod.fields)
            x0 = feed.peek()
            parts["first_batch_s"] = time.monotonic() - t0 - sum(parts.values())
            consumer.compile(x0)
            parts["compile_s"] = time.monotonic() - t0 - sum(parts.values())
            consumer.warm_up(feed.next)
            parts["warm_up_s"] = time.monotonic() - t0 - sum(parts.values())
            deadline = time.monotonic() + SINK_FILL_S
            while time.monotonic() < deadline:
                size, cap = list(pipe.queue_depths().values())[-1]
                if size >= cap:
                    break
                time.sleep(0.01)
            w = window(pipe, feed, consumer, seed, seconds, trace, int(config["check"]["window_batches"]))
            w["setup_s"] = w["t_start"] - t0
        memory_peak = max(device_peak_bytes(d.memory_stats() or {}) for d in chips)
        misplaced = max((misplaced_rows(x, chips) for _, x in w["sample"]), default=0)
        sample = [(k, jax.tree_util.tree_map(np.asarray, jax.device_get(x)))
                  for k, x in w.pop("sample")]
        consumer.free()

        ref_batch = feed_mod.reference_batches(config, traffic, n=n, batch=batch, seed=seed,
                                       shuffle=shuffle)
        numbers, readings = feed_mod.check(sample, ref_batch, calibrate)
        numbers.update(misplaced_rows=float(misplaced), failed_samples=float(w["failed"]))
        ctx = CheckContext(
            seed=seed, mesh=mesh, calibrate=calibrate, reference_batch=ref_batch,
            reference_module=lambda: load_module(
                spec["config_dir"] / config["reference"], "bench_reference"),
        )
        more, more_readings = consumer.check(ctx)
        numbers.update(more)
        for k, v in more_readings.items():
            readings.setdefault(k, {}).update(v)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    limits = {**{"misplaced_rows": 0.0, "failed_samples": 0.0}, **config["limits"]}
    checks = {k: {"value": float(v), "limit": float(limits[k])} for k, v in numbers.items()}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())

    from bench.peaks import peaks

    run = {
        **w, "chips": len(chips), "batch": batch, "config": config,
        "peaks": peaks(chips[0].device_kind) if platform == "tpu" else None,
    }
    chosen = spec["per_layer"] if trace else spec["end_to_end"]
    metrics = {}
    for m in chosen:
        reader = load_module(bench_dir / "metrics" / f"{m['name']}.py", f"bench_metric_{m['name']}")
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {
        "platform": chips[0].platform, "kind": chips[0].device_kind, "count": len(chips),
        "memory_peak_bytes": memory_peak,
    }
    result = {"correct": bool(correct), "attempted": int(w["samples"]), "failed": int(w["failed"]),
              "metrics": metrics, "device": device}
    if trace and w["trace"] is not None:
        device["busy_s"] = w["trace"]["busy_s"]
        device["window_s"] = w["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": w["trace"]["device_ops"], "idle_gaps": w["trace"]["idle_gaps"],
        }
    print(f"setup parts (s): {json.dumps(parts)}", file=sys.stderr, flush=True)
    if calibrate:
        result["readings"] = readings
    result["checks"] = checks
    return result


def window(pipe, feed: Feed, consumer, seed: int, seconds: float, trace: bool, keep: int) -> dict:
    """The measured window; returns what the metrics read."""
    import jax

    sample = Reservoir(keep, seed)
    before = stage_rows(pipe)
    done: list[float] = []
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracing = False
    cpu0 = cpu_seconds()
    t_start = time.monotonic()
    pending = None
    try:
        while True:
            if trace and not tracing and time.monotonic() - t_start >= seconds - TRACE_S - SETTLE_S:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1  # the annotations, not the runtime's own events
                opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing = True
            index = feed.taken
            x = feed.next()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                handle = consumer.dispatch(x)
            sample.offer(index, x)
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench.block"):
                    consumer.block(pending)
                done.append(time.monotonic())
            pending = handle
            if time.monotonic() - t_start >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench.block"):
            consumer.block(pending)
        done.append(time.monotonic())
        cpu1 = cpu_seconds()
        after = stage_rows(pipe)
        reduced = None
        if tracing:
            jax.profiler.stop_trace()
            tracing = False
            from bench import trace as trace_mod

            reduced = trace_mod.reduce(trace_mod.load(trace_dir), skip_s=SETTLE_S)
    finally:
        if tracing:
            with contextlib.suppress(Exception):
                jax.profiler.stop_trace()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    delta = stage_delta(before, after)
    t_end = done[-1]
    stamps = [t_start, *done]
    return {
        "t_start": t_start,
        "window_s": t_end - t_start,
        "steps": len(done),
        "samples": len(done) * consumer.batch,
        "intervals_s": [b - a for a, b in zip(stamps, stamps[1:])],
        "cpu_s": cpu1 - cpu0,
        "stages": delta,
        "failed": sum(d["num_failed"] for d in delta.values()),
        "trace": reduced,
        "sample": sample.items,
    }


def print_result(result: dict) -> None:
    """Each number compared beside its limit as the last lines of standard
    error, then the result as the last line of standard output."""
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
