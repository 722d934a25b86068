"""The on-chip decode's share of its roofline: the least bytes it must move
(uint8 crop window in, bfloat16 out) at the chip's HBM bandwidth, over the
device time of its program's operations in the trace
(``jit_dequant_normalize_augment``), not counting time an execution spends
waiting for its input with no operation running."""

from bench.arith import decode_least_bytes

PROGRAM = "jit_dequant_normalize_augment"


def read(run: dict):
    tr = run["trace"]
    if tr is None or run["peaks"] is None or not tr["program_s"].get(PROGRAM):
        return None
    rows = run["batch"] // run["chips"]
    least = tr["program_runs"][PROGRAM] * rows * decode_least_bytes(run["config"]["loader"]["out_hw"])
    return 100.0 * least / run["peaks"]["hbm_bytes_per_s"] / tr["program_s"][PROGRAM]
