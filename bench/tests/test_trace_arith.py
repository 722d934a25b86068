"""The trace reduction, the FLOP and byte counts, and the table of peaks.

Run explicitly: ``python -m pytest bench/tests``.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from bench import arith, harness, trace
from bench.peaks import peaks

DATA = pathlib.Path(__file__).parent / "data"
ROOT = pathlib.Path(__file__).resolve().parents[2]
VIT_B16 = json.loads((ROOT / "bench" / "configs" / "vit_b16-imagenet.json").read_text())["model"]


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "trace_vit_b16_1chip.json").read_text())


def _covered(events, lo, hi):
    """Brute force: 1 µs bins of [lo, hi) that some event touches."""
    bins = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            bins[int((a - lo) // 1000) : int(np.ceil((b - lo) / 1000))] = True
    return bins


def test_busy_and_idle_match_brute_force(recorded):
    r = trace.reduce(recorded)
    host = recorded["host"]
    lo, hi = min(s for _, s, _ in host), max(s + d for _, s, d in host)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    ops = recorded["devices"][0]["ops"]
    bins = _covered(ops, lo, hi)
    # each merged interval may gain up to one bin at either end
    slack = 2e-6 * len(trace.merged(ops, lo, hi))
    assert abs(r["busy_s"] - bins.sum() * 1e-6) <= slack
    idle = sum(g[1] - g[0] for g in trace.gaps(ops, lo, hi)) * 1e-9
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])
    assert 0.0 < r["busy_s"] < r["window_s"]


def test_program_time_by_stable_name(recorded):
    r = trace.reduce(recorded)
    host = recorded["host"]
    lo, hi = min(s for _, s, _ in host), max(s + d for _, s, d in host)
    mods = recorded["devices"][0]["modules"]
    ops = recorded["devices"][0]["ops"]
    # a program run counts where it lies wholly inside the window: the
    # step that began before the first host span does not
    assert any(n.startswith("jit_vit_b16_train_step(") and s < lo for n, s, _ in mods)
    assert "jit_vit_b16_train_step" not in r["program_s"]
    dec = [(s, d) for n, s, d in mods
           if n.startswith("jit_dequant_normalize_augment(") and lo <= s and s + d <= hi]
    assert r["program_runs"]["jit_dequant_normalize_augment"] == len(dec) == 2
    # its time is the operations it ran, 1 µs bins at the most off per run
    want = sum(_covered([o for o in ops if s <= o[1] < s + d], s, s + d).sum() for s, d in dec)
    assert r["program_s"]["jit_dequant_normalize_augment"] == pytest.approx(want * 1e-6, abs=4e-6)
    owners = {name.split("/")[0] for name, _ in r["device_ops"]}
    assert owners <= {"jit_vit_b16_train_step", "jit_dequant_normalize_augment"}


def test_program_time_leaves_out_the_wait_for_inputs():
    """On four chips the decode program is enqueued before its input has
    arrived: its execution spans 71.5 ms, of which its operations run 0.8.
    The step's operations fill its execution."""
    t = json.loads((DATA / "trace_vit_b16_4chip_dev0.json").read_text())
    r = trace.reduce(t)
    spans = {n.split("(")[0]: d * 1e-9 for n, _, d in t["devices"][0]["modules"]}
    dec, step = "jit_dequant_normalize_augment", "jit_vit_b16_train_step"
    assert spans[dec] == pytest.approx(0.0715, abs=1e-4)
    assert r["program_s"][dec] == pytest.approx(0.000805, abs=1e-6)
    assert r["program_s"][step] == pytest.approx(spans[step], rel=1e-3)
    run = {"trace": r, "batch": 512, "chips": 4, "peaks": peaks("TPU v5 lite"),
           "config": json.loads((ROOT / "bench" / "configs" / "vit_b16-imagenet.json").read_text())}
    mfu = harness.load_module(ROOT / "bench" / "metrics" / "vit_step_mfu.py", "m1").read(run)
    roof = harness.load_module(ROOT / "bench" / "metrics" / "decode_roofline.py", "m2").read(run)
    # 13.42 TFLOP per chip's step over 192.3 ms and 197 TFLOP/s
    assert mfu == pytest.approx(35.42, abs=0.01)
    # 57.8 MB per chip's decode over 0.805 ms and 819 GB/s
    assert roof == pytest.approx(8.77, abs=0.01)


def test_gaps_are_named_by_the_host_span_over_them(recorded):
    r = trace.reduce(recorded)
    names = [n for n, _ in r["idle_gaps"]]
    secs = [s for _, s in r["idle_gaps"]]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
    # the longest gap lies under the harness's wait for the step to finish
    assert names[0] == "bench.block"
    assert secs[0] > 1.0


def test_synthetic_union_gaps_and_attribution():
    t = {
        "devices": [
            {"name": "/device:TPU:0",
             "ops": [["a", 0, 10], ["b", 5, 10], ["c", 30, 10]],
             "modules": [["jit_x(1)", 0, 15], ["jit_y(2)", 30, 10]]},
            {"name": "/device:TPU:1", "ops": [["a", 0, 40]], "modules": []},
        ],
        "host": [["bench.get_batch", 0, 20], ["bench.block", 20, 30]],
    }
    r = trace.reduce(t)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx((25 + 40) / 2 * 1e-9)
    assert trace.gaps(t["devices"][0]["ops"], 0, 50) == [(15, 30), (40, 50)]
    assert r["idle_gaps"][0] == ["bench.block", pytest.approx(15e-9)]
    assert trace.attribute((15, 30), t["host"]) == "bench.block"
    assert r["program_runs"] == {"jit_x": 0.5, "jit_y": 0.5}
    assert r["program_s"] == {"jit_x": pytest.approx(7.5e-9), "jit_y": pytest.approx(5e-9)}


def test_reduce_finds_nothing_without_device_ops():
    assert trace.reduce({"devices": [], "host": [["bench.block", 0, 1]]}) is None


def test_vit_b16_flop_count():
    assert arith.vit_forward_flop(VIT_B16) / 1e9 == pytest.approx(34.94, abs=0.005)
    assert arith.vit_train_flop(VIT_B16) / 1e9 == pytest.approx(104.8, abs=0.05)
    # 13.42 TFLOP per step of 128
    assert arith.vit_train_flop(VIT_B16) * 128 / 1e12 == pytest.approx(13.42, abs=0.005)


def test_decode_least_bytes():
    assert arith.decode_least_bytes((224, 224)) == 451_584


def test_device_peak_counts_the_programs_reservation():
    # measured on one TPU v5e after three ViT-B/16 steps at batch 128: the
    # buffers peak at 1.11 GB, the step's temporaries are reserved apart
    stats = {"bytes_in_use": 791377408, "peak_bytes_in_use": 1108940800,
             "bytes_reserved": 11978555392, "peak_bytes_reserved": 11978555392}
    assert harness.device_peak_bytes(stats) == 1108940800 + 11978555392
    assert harness.device_peak_bytes({"peak_bytes_in_use": 7}) == 7


def test_peaks_table():
    v5e = peaks("TPU v5 lite")
    assert v5e == {"bf16_flop_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


def test_run_off_the_chip_exits_nonzero_with_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "iterate.mixed_sizes",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr


def test_run_without_the_program_exits_nonzero(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "c"))
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "iterate.mixed_sizes", "--seed", "5",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_settling_time_is_left_out():
    t = {
        "devices": [{"name": "/device:TPU:0", "ops": [["a", 0, 10], ["b", 100, 10]],
                     "modules": []}],
        "host": [["bench.block", 0, 90], ["bench.get_batch", 95, 20]],
    }
    r = trace.reduce(t, skip_s=50e-9)
    assert r["window_s"] == pytest.approx(20e-9)
    assert r["busy_s"] == pytest.approx(10e-9)
    # nothing left after the settling time: the whole trace is read
    assert trace.reduce(t, skip_s=1.0)["window_s"] == pytest.approx(115e-9)
