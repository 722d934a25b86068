"""Task time of the ``transfer`` stage (device_put and the decode's
dispatch) per batch over the window: the program's own counter."""


def read(run: dict):
    row = run["stages"].get("transfer")
    if not row or not row["num_out"]:
        return None
    return 1e3 * row["task_time"] / row["num_out"]
