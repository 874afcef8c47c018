"""Trace: mean wall time of fdfs.near.insert, a commit's row written to the
index on the device (the wait for the index's lock included)."""

from . import _near


def read(cell: dict):
    return _near.span_mean_ms(cell, "fdfs.near.insert")
