"""99th percentile of all downloads of the window, request to last byte."""

from ._latency import latencies_ms, percentile


def read(cell: dict):
    return percentile(latencies_ms(cell, "download"), 99)
