"""Queries answered per pass of the near-duplicate index: the increments
of ``near_queries`` over those of ``near_scans`` in the traced window (1 =
every query paid a pass of its own; see _near.py)."""

from . import _near


def read(cell: dict):
    got = _near.for_cell(cell)
    return got["queries"] / got["scans"] if got else None
