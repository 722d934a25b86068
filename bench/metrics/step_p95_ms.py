"""95th percentile of the interval between consecutive step completions,
over every step of the window (host clock)."""

import numpy as np


def read(run: dict) -> float:
    return float(np.percentile(run["intervals_s"], 95)) * 1e3
