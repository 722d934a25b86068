"""Task time of the fused read and decode phases per image over the
window: the program's own counters."""


def read(run: dict):
    read_row, dec = run["stages"].get("read"), run["stages"].get("decode")
    if not read_row or not dec or not dec["num_out"]:
        return None
    return 1e3 * (read_row["task_time"] + dec["task_time"]) / dec["num_out"]
