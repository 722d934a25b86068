"""Benchmark harness entry point: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (assignment contract).  The roofline
rows are derived from the dry-run artifacts under experiments/dryrun (run
``python -m repro.launch.dryrun`` first to refresh them).

Every bench runs in a child process of its own (``--bench NAME``), and
this parent never imports JAX: a device belongs to one process at a time,
so a parent that had touched it would leave its children none.

``--smoke`` runs each registered bench as a ~2-second CI sanity check:
modules whose ``run`` accepts a ``smoke`` flag shrink their workload; the
rest are given a 2-second soft budget and reported as ``_SMOKE_TIMEOUT``
rows (not failures) when they exceed it.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import os
import subprocess
import sys
import threading
import time
import traceback

SMOKE_BUDGET_S = 2.0
# A bench without a smoke flag ends its own process at the budget, padded
# by the interpreter/jax import time; a killed bench can't keep running
# behind the harness's back, so later rows are never contended.
SMOKE_IMPORT_GRACE_S = 45.0

#: (label, module) registry; modules are imported lazily and individually so
#: one module's missing dependency cannot take down the whole harness.
REGISTRY = [
    ("fig1/2 GIL scaling", "bench_gil_scaling"),
    ("fig5 loader throughput", "bench_loader_throughput"),
    ("table2 first batch", "bench_first_batch"),
    ("fig6/7 resources", "bench_resources"),
    ("fig8/9 e2e inference+training + ViT hot path", "bench_e2e"),
    ("table3 GIL modes", "bench_gil_modes"),
    ("appC video/decord", "bench_video"),
    ("wire format (beyond-paper)", "bench_wire_format"),
    ("zero-copy slab arena (beyond-paper)", "bench_zero_copy"),
    ("sharded record store (beyond-paper)", "bench_shards"),
    ("engine chunked+fused (beyond-paper)", "bench_engine"),
    ("fault recovery chaos (beyond-paper)", "bench_faults"),
    ("elastic shard fleet (beyond-paper)", "bench_fleet"),
    ("flight-recorder tracing (beyond-paper)", "bench_trace"),
    ("roofline (dry-run derived)", "roofline"),
]


def _tag(mod_name: str) -> str:
    label = dict((m, lbl) for lbl, m in REGISTRY)[mod_name]
    return label.replace(" ", "_")


def _run_bench(mod_name: str, smoke: bool) -> int:
    """Child side: import one bench, run it, print its CSV rows.

    Returns the exit code: 0 on success (and on a smoke timeout, which is
    a row, not a failure), 1 when the import or the run raised."""
    t_start = time.monotonic()
    tag = _tag(mod_name)
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    try:
        mod = importlib.import_module(f".{mod_name}", package=__package__)
    except Exception as e:
        traceback.print_exc()
        print(f"{tag}_IMPORT_FAILED,0,{e!r}", flush=True)
        return 1
    accepts_smoke = "smoke" in inspect.signature(mod.run).parameters
    if smoke and not accepts_smoke:

        def over_budget() -> None:
            print(f"{tag}_SMOKE_TIMEOUT,0,killed_over_{SMOKE_BUDGET_S}s_budget",
                  flush=True)
            os._exit(0)

        budget = SMOKE_BUDGET_S + SMOKE_IMPORT_GRACE_S - (time.monotonic() - t_start)
        timer = threading.Timer(max(budget, 0.0), over_budget)
        timer.daemon = True
        timer.start()
    try:
        rows = mod.run(smoke=True) if smoke and accepts_smoke else mod.run()
    except Exception as e:
        traceback.print_exc()
        print(f"{tag}_FAILED,0,{e!r}", flush=True)
        return 1
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}", flush=True)
    return 0


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--smoke",
        action="store_true",
        help="~2s per bench: CI sanity check, not a measurement",
    )
    parser.add_argument(
        "--bench", choices=[m for _, m in REGISTRY],
        help="run this one bench in this process (what the harness's children do)",
    )
    args = parser.parse_args(argv)
    if args.bench:
        sys.exit(_run_bench(args.bench, args.smoke))

    print("name,us_per_call,derived", flush=True)
    failures = 0
    for _label, mod_name in REGISTRY:
        cmd = [sys.executable, "-m", f"{__package__}.run", "--bench", mod_name]
        if args.smoke:
            cmd.append("--smoke")
        failures += subprocess.run(cmd).returncode != 0
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
