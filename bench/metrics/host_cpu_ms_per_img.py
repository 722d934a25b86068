"""Process CPU time (utime + stime, every thread) over the window, per image."""


def read(run: dict) -> float:
    return run["cpu_s"] / run["samples"] * 1e3
