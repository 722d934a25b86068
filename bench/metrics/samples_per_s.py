"""Images fed to the step per second, over the whole window (host clock)."""


def read(run: dict) -> float:
    return run["samples"] / run["window_s"]
