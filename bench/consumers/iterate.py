"""No model: the step is the wait for the decoded batch.

The loader's own ceiling (the SPDL paper's dataset-iteration benchmark):
each step blocks on a batch the on-chip decode produced, one batch behind
the newest, as a training loop that logs one step late would.
"""

from __future__ import annotations


class Consumer:
    setup_steps = 2

    def __init__(self, config: dict, mesh, seed: int, batch: int):
        self.batch = batch

    def compile(self, x) -> None:
        pass

    def dispatch(self, x):
        return x

    def block(self, handle) -> None:
        handle.block_until_ready()

    def warm_up(self, next_batch) -> int:
        for _ in range(self.setup_steps):
            self.block(self.dispatch(next_batch()))
        return self.setup_steps

    def free(self) -> None:
        pass

    def check(self, ctx) -> tuple[dict, dict]:
        """Nothing beyond the decoded batches, which the harness compares."""
        return {}, {}
