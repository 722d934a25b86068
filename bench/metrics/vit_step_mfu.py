"""The training step's share of one chip's bf16 peak, from the trace: the
FLOPs the forward and backward passes need for one chip's rows (counted
from the model's shapes) per step, over the device time of the step
program's operations (``jit_vit_b16_train_step``, its all-reduces
included) per step."""

from bench.arith import vit_train_flop

PROGRAM = "jit_vit_b16_train_step"


def read(run: dict):
    tr, model = run["trace"], run["config"].get("model")
    if tr is None or model is None or run["peaks"] is None or not tr["program_s"].get(PROGRAM):
        return None
    rows = run["batch"] // run["chips"]
    flop = vit_train_flop(model) * rows * tr["program_runs"][PROGRAM]
    return 100.0 * flop / tr["program_s"][PROGRAM] / run["peaks"]["bf16_flop_per_s"]
