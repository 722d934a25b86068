"""From a profiler trace to the numbers the metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a small
plain record (``from_profile``), which ``reduce`` reads:

* devices: one entry per accelerator plane, with its operations (the
  ``XLA Ops`` line) and its program executions (the ``XLA Modules`` line),
  each as ``[name, start_ns, duration_ns]``;
* host: the benchmark's own ``jax.profiler.TraceAnnotation`` spans, whose
  names start with ``bench.``, on the same clock.

The traced window runs from the first of those host spans (after an
optional settling time) to the end of the last.  Busy time is the union of a device's operation intervals inside
it, averaged over the devices; an idle gap is a stretch of the window with
no operation on the first device, named by the host span that overlaps it
most.  A program's time is the union of its operations' intervals within
each of its executions that lie wholly inside the window: an execution
that waits for its inputs with no operation running is not charged for
the wait.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

HOST_PREFIX = "bench."
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def from_profile(profile) -> dict:
    """The plain record of a ``jax.profiler.ProfileData``."""
    devices, host = [], []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            rec = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    rec[key] = [[_short(e.name), e.start_ns, e.duration_ns] for e in line.events]
            devices.append(rec)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events
                    if e.name.startswith(HOST_PREFIX)
                )
    devices.sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def _short(name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = bf16[...] ...``
    becomes ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(max(paths, key=os.path.getmtime)))


def merged(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of the events' intervals, clipped to ``[lo, hi]``."""
    spans = sorted(
        (max(s, lo), min(s + d, hi)) for _, s, d in events if s < hi and s + d > lo
    )
    out: list[list[float]] = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no event covers."""
    out, t = [], lo
    for s, e in merged(events, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(gap: tuple[float, float], host) -> str:
    """The host span that overlaps ``gap`` most, or ``"no host span"``."""
    best, name = 0.0, "no host span"
    for n, s, d in host:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, name = ov, n
    return name


def _module_base(name: str) -> str:
    return name.split("(", 1)[0]


def op_names(dev: dict) -> list[tuple[str, float, float]]:
    """Each operation as ``(module/op, start, duration)``, the module being
    the program execution that contains it."""
    mods = sorted(dev["modules"], key=lambda e: e[1])
    out, j = [], 0
    for name, s, d in sorted(dev["ops"], key=lambda e: e[1]):
        while j < len(mods) and mods[j][1] + mods[j][2] < s:
            j += 1
        owner = _module_base(mods[j][0]) if j < len(mods) and mods[j][1] <= s else "?"
        out.append((f"{owner}/{name}", s, d))
    return out


def program_time(dev: dict, lo: float, hi: float) -> dict[str, tuple[float, int]]:
    """Per program name, ``(seconds, runs)`` over its executions wholly
    inside ``[lo, hi]``: the union of the operations that start within
    each execution, clipped to it."""
    ops = sorted(dev["ops"], key=lambda e: e[1])
    starts = [s for _, s, _ in ops]
    out: dict[str, tuple[float, int]] = {}
    for name, s, dur in dev["modules"]:
        if lo <= s and s + dur <= hi:
            inside = ops[bisect.bisect_left(starts, s) : bisect.bisect_left(starts, s + dur)]
            t = sum(e - b for b, e in merged(inside, s, s + dur)) * 1e-9
            base = _module_base(name)
            secs, runs = out.get(base, (0.0, 0))
            out[base] = (secs + t, runs + 1)
    return out


def reduce(trace: dict, top: int = 10, skip_s: float = 0.0) -> dict | None:
    """Window, busy time, program time by name, top operations and the
    longest idle gaps; ``None`` where the trace holds no device operation
    or no host span.  The window leaves out the host spans that begin in
    the first ``skip_s`` seconds (all of them are kept where none is left)."""
    devices = [d for d in trace["devices"] if d["ops"]]
    host = trace["host"]
    if not devices or not host:
        return None
    start = min(s for _, s, _ in host) + skip_s * 1e9
    host = [e for e in host if e[1] >= start] or host
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    window = (hi - lo) * 1e-9
    busy = [sum(e - s for s, e in merged(d["ops"], lo, hi)) * 1e-9 for d in devices]
    program_s: dict[str, float] = {}
    program_n: dict[str, int] = {}
    op_s: dict[str, float] = {}
    for d in devices:
        for base, (secs, runs) in program_time(d, lo, hi).items():
            program_s[base] = program_s.get(base, 0.0) + secs
            program_n[base] = program_n.get(base, 0) + runs
        for name, s, dur in op_names(d):
            if lo <= s < hi:
                op_s[name] = op_s.get(name, 0.0) + dur * 1e-9
    n = len(devices)
    idle = sorted(gaps(devices[0]["ops"], lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "chips": n,
        "window_s": window,
        "busy_s": sum(busy) / n,
        "program_s": {k: v / n for k, v in program_s.items()},
        "program_runs": {k: v / n for k, v in program_n.items()},
        "device_ops": [
            [k, v / n] for k, v in sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [[attribute(g, host), (g[1] - g[0]) * 1e-9] for g in idle],
    }
