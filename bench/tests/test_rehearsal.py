"""Rehearsals of the cells on the CPU at tiny sizes, through the harness.

The harness's look for a chip is skipped (``platform="cpu"``); the rest of
a run is the real one: corpus, loader, consumer, window, check, metrics.
The faults are planted underneath the timed path and must turn
``correct`` false; the control must fail the configured limits.

Run explicitly: ``python -m pytest bench/tests``.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny

CONTRACT = ["correct", "attempted", "failed", "metrics", "device", "checks"]
SEED = 3_000_000_007  # past 2**31: seeds of any size must work


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tiny.make_root(tmp_path_factory.mktemp("tiny"))
    old = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(r / "jax_cache")
    yield r
    if old is None:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = old


def run(root, workload, *, seed=SEED, trace=False, calibrate=False) -> dict:
    return harness.run_cell(workload, seed, 1.5, trace, t0=time.monotonic(),
                            platform="cpu", root=root, calibrate=calibrate)


def test_iterate_result_line(root, capsys):
    r = run(root, "it.1")
    harness.print_result(r)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == CONTRACT
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"samples_per_s", "step_p95_ms", "host_cpu_ms_per_img",
                                    "setup_s"}
    assert line["device"]["count"] == 1 and line["device"]["kind"]
    assert line["checks"]["decode_err_ulp"]["value"] == 0.0
    assert err.strip().splitlines()[-1].startswith("check failed_samples")


def test_traced_run_reports_the_counters(root):
    r = run(root, "it.1", trace=True)
    assert r["correct"]
    assert {"transfer_ms_per_batch", "host_decode_ms_per_img",
            "decode_occupancy_pct"} <= set(r["metrics"])
    # the CPU has no device plane: the device metrics find nothing to read
    assert "device_idle_pct" not in r["metrics"] and "decode_roofline" not in r["metrics"]


def test_vit_one_device_is_correct(root):
    r = run(root, "vit.1")
    assert list(r) == CONTRACT and r["correct"], r["checks"]
    assert {"loss_gap", "grad_norm_gap", "update_norm_gap"} <= set(r["checks"])


def test_control_fails_the_limits(root):
    for workload in ("it.1", "vit.1", "native.1", "shards.1"):
        r = run(root, workload, calibrate=True)
        limits = {k: c["limit"] for k, c in r["checks"].items()}
        control = r["readings"]["control"]
        assert any(v > limits[k] for k, v in control.items()), (workload, control, limits)


@pytest.mark.parametrize("workload", ["native.1", "shards.1"])
def test_stored_and_sharded_records_are_correct(root, workload):
    r = run(root, workload)
    assert list(r) == CONTRACT and r["correct"], r["checks"]
    assert r["failed"] == 0 and r["checks"]["decode_err_ulp"]["value"] == 0.0
    assert set(r["metrics"]) == {"samples_per_s", "step_p95_ms", "host_cpu_ms_per_img", "setup_s"}


def test_shards_hold_the_records_and_no_files(root, tmp_path):
    from repro.data.shards import ShardDataset

    spec = harness.load_cell("shards.1", root)
    feed = harness.load_module(spec["feed"], "feed_under_test")
    feed.write(tmp_path, spec["config"], spec["traffic"], 64, SEED, threads=2)
    assert sorted(p.suffix for p in tmp_path.iterdir()) == [".json", ".rpshard"]
    from bench import corpus

    blobs = corpus.encode_records(spec["traffic"], 64, SEED, threads=2)
    ds = ShardDataset(tmp_path)
    try:
        assert [bytes(ds.read_fields(i, ("image",))["image"]) for i in range(64)] == blobs
    finally:
        ds.close()


def test_a_corrupt_record_in_a_shard_is_not_correct(root, monkeypatch):
    """One record's bytes altered in its shard after packing: the loader's
    read fails it, and the run reads false."""
    real = harness.load_module

    def load(path, name):
        mod = real(path, name)
        if name == "bench_feed_image_shards":
            write = mod.write

            def corrupt(workdir, config, traffic, n, seed, threads):
                write(workdir, config, traffic, n, seed, threads)
                from bench import corpus

                blob = corpus.encode_records(traffic, n, seed, threads)[5]
                shard = next(pathlib.Path(workdir).glob("*.rpshard"))
                data = bytearray(shard.read_bytes())
                at = data.find(blob) + len(blob) // 2
                data[at] ^= 0xFF
                shard.write_bytes(bytes(data))

            mod.write = corrupt
        return mod

    monkeypatch.setattr(harness, "load_module", load)
    r = run(root, "shards.1")
    assert not r["correct"], r["checks"]
    assert r["checks"]["failed_samples"]["value"] > 0


def _alter_decode(monkeypatch, how):
    import repro.kernels.ops as ops

    real = ops.dequant_normalize_augment

    def broken(*args, **kwargs):
        return how(real(*args, **kwargs))

    monkeypatch.setattr(ops, "dequant_normalize_augment", broken)


@pytest.mark.parametrize("workload", ["it.1", "vit.1", "native.1", "shards.1"])
@pytest.mark.parametrize("fault", ["answer_altered", "half_batch_repeated"])
def test_broken_decode_is_not_correct(root, monkeypatch, workload, fault):
    how = {
        "answer_altered": lambda y: y.at[0, 0, 0, 0].add(jnp.asarray(0.25, y.dtype)),
        "half_batch_repeated": lambda y: jnp.concatenate([y[: y.shape[0] // 2]] * 2),
    }[fault]
    _alter_decode(monkeypatch, how)
    r = run(root, workload)
    assert not r["correct"]
    assert r["checks"]["decode_err_ulp"]["value"] > r["checks"]["decode_err_ulp"]["limit"]


def _unchanged(mod):
    def make_step(model, lr):
        def vit_b16_train_step(params, x, labels):
            return params, mod.loss(model, params, x, labels)

        return jax.jit(vit_b16_train_step)

    return make_step


def _half_batch(mod):
    def make_step(model, lr):
        def vit_b16_train_step(params, x, labels):
            h = x.shape[0] // 2
            value, g = jax.value_and_grad(lambda p: mod.loss(model, p, x[:h], labels[:h]))(params)
            return jax.tree_util.tree_map(lambda a, b: a - lr * b, params, g), value

        return jax.jit(vit_b16_train_step)

    return make_step


@pytest.mark.parametrize("fault", [_unchanged, _half_batch], ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(root, monkeypatch, fault):
    real = harness.load_module

    def load(path, name):
        mod = real(path, name)
        if name == "bench_consumer_vit_b16":
            mod.make_step = fault(mod)
        return mod

    monkeypatch.setattr(harness, "load_module", load)
    r = run(root, "vit.1")
    assert not r["correct"], r["checks"]


_CHILD = textwrap.dedent("""
    import json, pathlib, sys, time
    import jax, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from bench import harness
    root, fault = pathlib.Path(sys.argv[1]), sys.argv[2]
    real = harness.load_module

    def no_exchange(mod):
        def make_step(model, lr):
            mesh = Mesh(np.array(jax.devices()[:4]), ("data",))

            def local(params, x, labels):
                value, g = jax.value_and_grad(lambda p: mod.loss(model, p, x, labels))(params)
                return jax.tree_util.tree_map(lambda a, b: a - lr * b, params, g), value

            return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P(), P("data"), P("data")),
                                         out_specs=(P(), P()), check_vma=False))
        return make_step

    def load(path, name):
        mod = real(path, name)
        if fault == "no_exchange" and name == "bench_consumer_vit_b16":
            mod.make_step = no_exchange(mod)
        return mod

    harness.load_module = load
    r = harness.run_cell("vit.4", 3000000011, 1.5, False, t0=time.monotonic(),
                         platform="cpu", root=root)
    harness.print_result(r)
""")


@pytest.mark.parametrize("fault", ["none", "no_exchange"])
def test_four_devices_in_a_child(root, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(tiny.REPO), str(tiny.REPO / "src")]))
    p = subprocess.run([sys.executable, "-c", _CHILD, str(root), fault],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line) == CONTRACT
    assert line["device"]["count"] == 4
    assert line["checks"]["misplaced_rows"]["value"] == 0.0
    assert line["correct"] is (fault == "none"), line["checks"]


def test_feed_waits_for_the_batch_in_hand_before_the_next():
    """The loader recycles a batch's host memory a fixed number of batches
    after its copy is issued: one batch is taken at a time, and only once
    the one before it is on the device."""
    events = []

    class Batch:
        def __init__(self, i):
            self.i = i

        def block_until_ready(self):
            events.append(("ready", self.i))
            return self

    class Pipe:
        n = 0

        def get_item(self):
            self.n += 1
            events.append(("get", self.n))
            return {"images": Batch(self.n)}

    feed = harness.Feed(Pipe(), ("images",))
    assert feed.peek().i == 1 and feed.next().i == 1
    assert [feed.next().i, feed.next().i] == [2, 3]
    assert events == [("get", 1), ("ready", 1), ("ready", 1), ("get", 2), ("ready", 2), ("get", 3)]
    assert feed.taken == 3


def test_a_cell_is_added_from_files_alone(root, tmp_path):
    """A new traffic mix, configuration, cell and per-layer metric, each a
    file of its own plus its entry in BENCHMARK.json; no harness edit."""
    new = tiny.make_root(tmp_path / "r")
    traffic = json.loads((new / "bench" / "traffic" / "tiny.json").read_text())
    traffic["record_sizes"]["rows"] = [[48, 64, 1.0]]
    (new / "bench" / "traffic" / "square48.json").write_text(json.dumps(traffic))
    cfg = json.loads((new / "bench" / "configs" / "tiny-iterate.json").read_text())
    cfg.update(name="tiny-iterate-16", batch_per_chip=16)
    (new / "bench" / "configs" / "tiny-iterate-16.json").write_text(json.dumps(cfg))
    (new / "bench" / "metrics" / "batches_per_s.py").write_text(
        'def read(run):\n    return run["steps"] / run["window_s"]\n')
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-iterate-16", "source": "test",
                            "file": "bench/configs/tiny-iterate-16.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "it16.square", "config": "tiny-iterate-16",
                              "traffic": "square48", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "batches_per_s", "unit": "1/s", "better": "higher",
                              "source": "host_clock", "layer": "consumer step",
                              "moves": "samples_per_s", "workloads": ["it16.square"]})
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    r = harness.run_cell("it16.square", SEED, 1.5, True, t0=time.monotonic(),
                         platform="cpu", root=new)
    assert r["correct"]
    assert r["metrics"]["batches_per_s"]["value"] == pytest.approx(
        r["attempted"] / 16 / (r["attempted"] / 16 / r["metrics"]["batches_per_s"]["value"]))
    assert r["attempted"] % 16 == 0


_PAIR_FEED = '''
"""Images and an int32 token field in one batch: the image loader's batch
with each batch's tokens placed beside it, on the same rows."""
import numpy as np

from bench.feeds import image_files

fields = ("images", "tokens")
SEQ = 16
write = image_files.write


def tokens_of(k, batch):
    return (np.arange(batch * SEQ, dtype=np.int32).reshape(batch, SEQ) * 7 + k) % 1000


class Pair:
    def __init__(self, pipe, shardings, batch):
        self.pipe, self.shardings, self.batch, self.k = pipe, shardings, batch, 0

    def get_item(self):
        import jax

        item = dict(self.pipe.get_item())
        item["tokens"] = jax.device_put(tokens_of(self.k, self.batch), self.shardings)
        self.k += 1
        return item

    def stats(self):
        return self.pipe.stats()

    def queue_depths(self):
        return self.pipe.queue_depths()

    def auto_stop(self):
        return self.pipe.auto_stop()


def build(workdir, config, traffic, *, batch, seed, shardings, sampler):
    pipe = image_files.build(workdir, config, traffic, batch=batch, seed=seed,
                             shardings=shardings, sampler=sampler)
    return Pair(pipe, shardings, batch)


def reference_batches(config, traffic, *, n, batch, seed, shuffle):
    images = image_files.reference_batches(config, traffic, n=n, batch=batch, seed=seed,
                                           shuffle=shuffle)
    return lambda k, dtype=np.float32: {"images": images(k, dtype), "tokens": tokens_of(k, batch)}


def check(sample, batch_at, calibrate):
    numbers, readings = image_files.check(
        [(k, x["images"]) for k, x in sample],
        lambda k, dtype=np.float32: batch_at(k, dtype)["images"], calibrate)
    numbers["token_mismatches"] = float(sum(
        int((x["tokens"] != batch_at(k)["tokens"]).sum()) for k, x in sample))
    return numbers, readings
'''

_PAIR_CONSUMER = '''
"""A step that takes a dict of both fields and waits on the tokens."""


class Consumer:
    setup_steps = 2

    def __init__(self, config, mesh, seed, batch):
        self.batch = batch

    def compile(self, x):
        self.dispatch(x)

    def dispatch(self, x):
        if not isinstance(x, dict) or set(x) != {"images", "tokens"}:
            raise TypeError(f"expected a dict of images and tokens, got {type(x)}")
        return x

    def block(self, handle):
        import jax

        jax.block_until_ready(handle)

    def warm_up(self, next_batch):
        for _ in range(self.setup_steps):
            self.block(self.dispatch(next_batch()))
        return self.setup_steps

    def free(self):
        pass

    def check(self, ctx):
        ref = ctx.reference_batch(0)
        return {"reference_fields_missing": float(set(ref) != {"images", "tokens"})}, {}
'''


@pytest.mark.parametrize("tokens_altered", [False, True])
def test_a_two_field_feed_is_added_from_files_alone(tmp_path, monkeypatch, tokens_altered):
    """A feed whose batches carry images and int32 tokens, its consumer,
    configuration and cell, each a file of its own plus its entry in
    BENCHMARK.json; no harness edit.  The consumer gets a dict, each field's
    rows are checked for their place, and an altered token reads false."""
    new = tiny.make_root(tmp_path / "r")
    (new / "bench" / "feeds" / "pair.py").write_text(_PAIR_FEED)
    (new / "bench" / "consumers" / "pair_probe.py").write_text(_PAIR_CONSUMER)
    cfg = json.loads((new / "bench" / "configs" / "tiny-iterate.json").read_text())
    cfg.update(name="tiny-pair", feed="pair", consumer="pair_probe")
    cfg["limits"].update(token_mismatches=0.0, reference_fields_missing=0.0)
    (new / "bench" / "configs" / "tiny-pair.json").write_text(json.dumps(cfg))
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-pair", "source": "test",
                            "file": "bench/configs/tiny-pair.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "pair.1", "config": "tiny-pair", "traffic": "tiny",
                              "chips": 1, "why": "test"})
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    if tokens_altered:
        real = harness.load_module

        def load(path, name):
            mod = real(path, name)
            if name == "bench_feed_pair":
                honest = mod.tokens_of
                mod.tokens_of = lambda k, batch: honest(k, batch) + 1
                real_ref = mod.reference_batches

                def ref(*args, **kw):
                    mod.tokens_of = honest  # the reference keeps the true tokens
                    return real_ref(*args, **kw)

                mod.reference_batches = ref
            return mod

        monkeypatch.setattr(harness, "load_module", load)
    r = harness.run_cell("pair.1", SEED, 1.5, False, t0=time.monotonic(),
                         platform="cpu", root=new, calibrate=True)
    assert r["checks"]["misplaced_rows"]["value"] == 0.0
    assert r["checks"]["decode_err_ulp"]["value"] == 0.0
    assert r["checks"]["reference_fields_missing"]["value"] == 0.0
    assert (r["checks"]["token_mismatches"]["value"] > 0) is tokens_altered
    assert r["correct"] is not tokens_altered, r["checks"]
