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
    for workload in ("it.1", "vit.1"):
        r = run(root, workload, calibrate=True)
        limits = {k: c["limit"] for k, c in r["checks"].items()}
        control = r["readings"]["control"]
        assert any(v > limits[k] for k, v in control.items()), (workload, control, limits)


def _alter_decode(monkeypatch, how):
    import repro.kernels.ops as ops

    real = ops.dequant_normalize_augment

    def broken(*args, **kwargs):
        return how(real(*args, **kwargs))

    monkeypatch.setattr(ops, "dequant_normalize_augment", broken)


@pytest.mark.parametrize("workload", ["it.1", "vit.1"])
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

    feed = harness.Feed(Pipe())
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
