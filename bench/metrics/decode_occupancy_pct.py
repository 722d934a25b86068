"""Share of the fused read and decode workers' time spent in their tasks
over the window: task time over window times concurrency."""


def read(run: dict):
    read_row, dec = run["stages"].get("read"), run["stages"].get("decode")
    if not read_row or not dec:
        return None
    workers = max(read_row["concurrency"], dec["concurrency"])
    return 100.0 * (read_row["task_time"] + dec["task_time"]) / (run["window_s"] * workers)
