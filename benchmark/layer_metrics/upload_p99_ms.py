"""99th percentile of all uploads of the window, send to file id returned."""

from ._latency import latencies_ms, percentile


def read(cell: dict):
    return percentile(latencies_ms(cell, "upload"), 99)
