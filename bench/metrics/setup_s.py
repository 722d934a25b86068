"""Process start to the first timed step (host clock)."""


def read(run: dict) -> float:
    return run["setup_s"]
