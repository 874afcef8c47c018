"""Device trace: 1 - (union of the device's operation intervals) over the
traced window, which is the whole measured window."""


def read(cell: dict):
    if not cell.get("trace"):
        return None
    return 100.0 * (1.0 - cell["trace"]["busy_s"] / cell["trace_window_s"])
