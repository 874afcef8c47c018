"""Median of the window's near_dups operations, request to parsed reply,
by the clients' clocks."""

from ._latency import latencies_ms, percentile


def read(cell: dict):
    return percentile(latencies_ms(cell, "near_dups"), 50)
