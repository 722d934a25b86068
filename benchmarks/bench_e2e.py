"""Paper Fig 8/9: end-to-end throughput with model inference and training,
plus the dummy-loader MAX bound (Fig 9's key claim: SPDL ≈ MAX, i.e. the
loader never starves the accelerator step) — and the hot-path-to-device
proof: a ViT-B/16-shaped synthetic training step fed by the full image
loader (uint8 wire + chunked sink drain + on-chip fused decode).

The image section records two acceptance gates in ``BENCH_e2e.json``:

* **zero starvation** — accumulated ``get_items`` wait across the
  measured steps is ≤ 1% of wall time (the step never waits on data);
* **host CPU** — draining an epoch through the uint8-wire + device-decode
  path costs ≥ 1.5× less process CPU time than the host-decode baseline
  (same loader, float decode tail on the consumer thread), because the
  host never touches a pixel float.

``python -m benchmarks.bench_e2e --gate`` re-checks both at reduced size
and exits nonzero on regression (CI).  ``--smoke`` shrinks everything.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import tempfile
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import (
    SyntheticImageDataset,
    SyntheticTokenDataset,
    build_image_loader,
    build_lm_loader,
)
from repro.data.transfer import DeviceDecode

from . import vit

OUT_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_e2e.json"

SEQ_LEN, LM_BATCH = 64, 8
STEPS = 20

# -- ViT-B/16-shaped image workload (true /16 patching; width/depth scaled
# -- for a CPU box — the tokens-per-image and data path are the real thing)
IMG_HW = (224, 224)
VIT = vit.ViTConfig(d_model=128, depth=2, heads=4, mlp=512, n_classes=10)
IMG_BATCH = 8
IMG_N = 64  # dataset size → 8 batches/epoch
IMG_STEPS = 24
CPU_EPOCHS = 3  # epochs per CPU-time drain: widen past /proc's 10ms ticks
MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
GATE_STARVATION_MAX = 0.01
GATE_CPU_SPEEDUP_MIN = 1.5


def _mk():
    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import build_train_step
    from repro.optim import init_opt_state

    shape = ShapeConfig("bench_train", seq_len=SEQ_LEN, global_batch=LM_BATCH, kind="train")
    cfg = get_smoke_config("olmo-1b")
    # donate=False: the bench reuses (params, opt) across loops
    bundle = build_train_step(cfg, None, shape, donate=False)
    params = bundle.model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(bundle.opt_cfg, params)
    ds = SyntheticTokenDataset(400, vocab=cfg.vocab_size, min_len=32, max_len=160)
    return cfg, bundle, params, opt, ds


def _loop(bundle, params, opt, batches) -> float:
    t0 = time.monotonic()
    n = 0
    for batch in batches:
        params, opt, metrics = bundle.jitted(params, opt, batch)
        n += 1
    jax.block_until_ready(metrics["loss"])
    return n * LM_BATCH * SEQ_LEN / (time.monotonic() - t0)


def _lm_rows(steps: int) -> list[tuple[str, float, str]]:
    try:
        cfg, bundle, params, opt, ds = _mk()
    except (ImportError, ModuleNotFoundError) as e:
        # the LM model stack is optional here; the image section below is
        # self-contained and still runs (and carries the gates)
        return [("fig8/9_lm_skipped", 0.0, f"model_stack_unavailable:{type(e).__name__}")]
    rows = []

    # -- MAX: dummy loader (one batch reused; zero loading cost) ----------
    rng = np.random.default_rng(0)
    fake = {
        "tokens": rng.integers(0, cfg.vocab_size, (LM_BATCH, SEQ_LEN)).astype(np.int32),
        "labels": rng.integers(0, cfg.vocab_size, (LM_BATCH, SEQ_LEN)).astype(np.int32),
        "positions": np.tile(np.arange(SEQ_LEN, dtype=np.int32), (LM_BATCH, 1)),
        "segment_ids": np.zeros((LM_BATCH, SEQ_LEN), np.int32),
    }
    _loop(bundle, params, opt, [fake] * 3)  # warmup/compile
    tps_max = _loop(bundle, params, opt, [fake] * steps)
    rows.append(("fig9_train_MAX_dummy", 1e6 / tps_max, f"{tps_max:.0f}tok/s"))

    # -- SPDL-fed training --------------------------------------------------
    pipe, _ = build_lm_loader(ds, seq_len=SEQ_LEN, batch_size=LM_BATCH, num_threads=4)
    with pipe.auto_stop():
        it = iter(pipe)
        batches = [next(it) for _ in range(steps)]  # prefetch check below uses live feed
        tps_spdl = _loop(bundle, params, opt, batches)
    rows.append(
        ("fig9_train_spdl", 1e6 / tps_spdl, f"{tps_spdl:.0f}tok/s;{tps_spdl / tps_max:.0%}_of_MAX")
    )

    # live-fed (loader concurrent with steps, the honest fig9 measurement)
    pipe2, _ = build_lm_loader(ds, seq_len=SEQ_LEN, batch_size=LM_BATCH, num_threads=4)
    with pipe2.auto_stop():
        it = iter(pipe2)
        t0 = time.monotonic()
        for _ in range(steps):
            batch = next(it)
            params, opt, m = bundle.jitted(params, opt, batch)
        jax.block_until_ready(m["loss"])
        dt = time.monotonic() - t0
    tps_live = steps * LM_BATCH * SEQ_LEN / dt
    rows.append(
        ("fig9_train_spdl_live", 1e6 / tps_live, f"{tps_live:.0f}tok/s;{tps_live / tps_max:.0%}_of_MAX")
    )

    # -- Fig 8: inference (prefill) fed by the pipeline ---------------------
    from repro.configs.base import ShapeConfig
    from repro.launch.steps import build_prefill_step

    pshape = ShapeConfig("bench_infer", 64, 8, "prefill")
    pb = build_prefill_step(cfg, None, pshape)
    pipe3, _ = build_lm_loader(ds, seq_len=64, batch_size=8, num_threads=4)
    infer_steps = max(4, steps // 2)
    with pipe3.auto_stop():
        it = iter(pipe3)
        first = next(it)
        jax.block_until_ready(pb.jitted(params, {"tokens": first["tokens"]})[0])  # compile
        t0 = time.monotonic()
        for _ in range(infer_steps):
            batch = next(it)
            logits, _ = pb.jitted(params, {"tokens": batch["tokens"]})
        jax.block_until_ready(logits)
        dt = time.monotonic() - t0
    fps = infer_steps * 8 / dt
    rows.append(("fig8_infer_spdl", 1e6 / fps, f"{fps:.1f}seq/s"))
    return rows


def _proc_cpu_s() -> float:
    """Process CPU seconds (utime + stime, all threads) from /proc."""
    parts = open("/proc/self/stat").read().split()
    return (int(parts[13]) + int(parts[14])) / os.sysconf("SC_CLK_TCK")


def _host_decode_tail(images: np.ndarray) -> jax.Array:
    """The baseline the fused kernel replaces: the classic host-side float
    decode tail — uint8 → f32 /255, per-channel normalize, NCHW transpose,
    contiguous copy — then the (4× fatter) device_put."""
    x = images.astype(np.float32) / 255.0
    x -= np.asarray(MEAN, np.float32)
    x /= np.asarray(STD, np.float32)
    x = np.ascontiguousarray(x.transpose(0, 3, 1, 2))
    return jax.device_put(x)


def _image_loader(ds, hw, *, device_decode: bool, epochs):
    dd = (
        DeviceDecode(mean=MEAN, std=STD)
        if device_decode
        else None
    )
    return build_image_loader(
        ds,
        batch_size=IMG_BATCH,
        hw=hw,
        epochs=epochs,
        num_threads=6,
        read_concurrency=3,
        decode_concurrency=3,
        sink_buffer=3,
        uint8_wire=True,
        device_decode=dd,
        transfer_chunk=2,
    )


def _measure_starvation(ds, hw, steps: int) -> dict:
    """Live-fed ViT training: the loader runs concurrently with the step;
    the gate is the accumulated time the step spent waiting in get_items
    after warmup (≤ 1% of wall = the loader never starves the step)."""
    params = vit.init(VIT, jax.random.PRNGKey(0), hw)
    step = vit.make_step(VIT)
    labels = jnp.asarray(np.arange(IMG_BATCH) % VIT.n_classes, jnp.int32)
    pipe = _image_loader(ds, hw, device_decode=True, epochs=None)
    stash: deque = deque()
    wait = 0.0

    def next_batch():
        nonlocal wait
        if not stash:
            t0 = time.monotonic()
            stash.extend(pipe.get_items(2))
            wait += time.monotonic() - t0
        return stash.popleft()

    with pipe.auto_stop():
        pipe.start()
        for _ in range(2):  # compile + fill the sink
            params, loss = step(params, next_batch()["images"], labels)
        jax.block_until_ready(loss)
        wait = 0.0
        t0 = time.monotonic()
        for _ in range(steps):
            params, loss = step(params, next_batch()["images"], labels)
        jax.block_until_ready(loss)
        wall = time.monotonic() - t0
        snaps = pipe.stats()
    return {
        "steps": steps,
        "wall_s": wall,
        "step_wait_s": wait,
        "starvation_frac": wait / wall,
        "sink_drained_chunks": snaps[-1].sink_drained_chunks,
        "device_decode_batches": next(
            s.device_decode_batches for s in snaps if s.name == "transfer"
        ),
    }


def _measure_cpu_epoch(ds, hw, *, device_decode: bool, epochs: int = CPU_EPOCHS) -> dict:
    """Process CPU time to drain ``epochs`` of ready-to-train batches.

    device_decode=True: uint8 wire + fused on-chip decode (zero host float
    math).  False: the same loader, host-side float decode tail per batch
    (what every host-decode pipeline pays)."""
    # warm compile caches outside the measured window
    warm = np.zeros((IMG_BATCH, *hw, 3), np.uint8)
    if device_decode:
        from repro.kernels.ops import dequant_normalize_augment

        jax.block_until_ready(
            dequant_normalize_augment(
                jnp.asarray(warm),
                jnp.asarray(MEAN, jnp.float32),
                jnp.asarray(STD, jnp.float32),
            )
        )
    else:
        jax.block_until_ready(_host_decode_tail(warm))

    pipe = _image_loader(ds, hw, device_decode=device_decode, epochs=epochs)
    batches = 0
    t0 = time.monotonic()
    c0 = _proc_cpu_s()
    with pipe.auto_stop():
        pipe.start()
        while True:
            try:
                chunk = pipe.get_items(2)
            except StopIteration:
                break
            for b in chunk:
                out = (
                    b["images"]
                    if device_decode
                    else _host_decode_tail(np.asarray(b["images"]))
                )
                jax.block_until_ready(out)  # the decode must actually run
                batches += 1
    cpu = _proc_cpu_s() - c0
    wall = time.monotonic() - t0
    return {"batches": batches, "cpu_s": cpu, "wall_s": wall,
            "cpu_s_per_batch": cpu / max(batches, 1)}


def _image_section(smoke: bool) -> dict:
    hw = (64, 64) if smoke else IMG_HW
    n = 16 if smoke else IMG_N
    steps = 4 if smoke else IMG_STEPS
    epochs = 1 if smoke else CPU_EPOCHS
    with tempfile.TemporaryDirectory() as d:
        ds = SyntheticImageDataset.materialize(d, n, hw=hw, seed=0)
        starv = _measure_starvation(ds, hw, steps)
        # interleave-free A/B: each drain is a fresh bounded pipeline
        host = _measure_cpu_epoch(ds, hw, device_decode=False, epochs=epochs)
        wire = _measure_cpu_epoch(ds, hw, device_decode=True, epochs=epochs)
    speedup = host["cpu_s"] / max(wire["cpu_s"], 1e-9)
    return {
        "workload": {
            "hw": list(hw), "patch": VIT.patch,
            "tokens_per_image": (hw[0] // VIT.patch) * (hw[1] // VIT.patch),
            "d_model": VIT.d_model, "depth": VIT.depth, "heads": VIT.heads,
            "batch_size": IMG_BATCH, "dataset_items": n, "steps": steps,
        },
        "starvation": starv,
        "cpu_epoch_host_decode": host,
        "cpu_epoch_device_decode": wire,
        "host_cpu_speedup": speedup,
        "gates": {
            "starvation_frac_max": GATE_STARVATION_MAX,
            "starvation_frac": starv["starvation_frac"],
            "starvation_ok": starv["starvation_frac"] <= GATE_STARVATION_MAX,
            "host_cpu_speedup_min": GATE_CPU_SPEEDUP_MIN,
            "host_cpu_speedup": speedup,
            "host_cpu_ok": speedup >= GATE_CPU_SPEEDUP_MIN,
        },
    }


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    rows = _lm_rows(4 if smoke else STEPS)
    img = _image_section(smoke)
    g = img["gates"]
    rows.append(
        (
            "e2e_vit_starvation",
            img["starvation"]["step_wait_s"] * 1e6 / max(img["starvation"]["steps"], 1),
            f"{g['starvation_frac']:.3%}_of_wall;gate<= {GATE_STARVATION_MAX:.0%}",
        )
    )
    rows.append(
        (
            "e2e_vit_host_cpu",
            img["cpu_epoch_device_decode"]["cpu_s_per_batch"] * 1e6,
            f"x{g['host_cpu_speedup']:.2f}_less_host_cpu;gate>=x{GATE_CPU_SPEEDUP_MIN:.1f}",
        )
    )
    if not smoke:  # persist only full runs; smoke numbers are noise
        OUT_PATH.write_text(json.dumps(img, indent=2) + "\n")
    return rows


def check_gate() -> int:
    """CI gate: both image-section gates at reduced size, nonzero on fail."""
    global IMG_STEPS
    IMG_STEPS = 12  # CI-budget sized; full hw/dataset keeps the signal real
    img = _image_section(smoke=False)
    g = img["gates"]
    print(
        f"e2e gate: starvation {g['starvation_frac']:.3%} "
        f"(<= {GATE_STARVATION_MAX:.0%}), host-CPU x{g['host_cpu_speedup']:.2f} "
        f"(>= x{GATE_CPU_SPEEDUP_MIN:.1f})"
    )
    ok = True
    if not g["starvation_ok"]:
        print(f"REGRESSION: step wait {g['starvation_frac']:.3%} of wall exceeds gate")
        ok = False
    if not g["host_cpu_ok"]:
        print(f"REGRESSION: host-CPU speedup x{g['host_cpu_speedup']:.2f} below gate")
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    if "--gate" in sys.argv:
        sys.exit(check_gate())
    for r in run("--smoke" in sys.argv):
        print(",".join(map(str, r)))
