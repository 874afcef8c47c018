"""Trace: mean wall time of fdfs.near.scan, one pass of the index from its
dispatch to its result on the host."""

from . import _near


def read(cell: dict):
    return _near.span_mean_ms(cell, "fdfs.near.scan")
