"""Trace: over the window's fdfs.engine.dispatch spans, the rows that held a
chunk over the lanes the SHA-1 kernel ran them on (sum of ``rows`` / sum of
``lanes``): 1 where every lane of every launch carried a chunk."""

from . import _dispatch


def read(cell: dict):
    got = _dispatch.for_cell(cell)
    if not got or not got["lanes"]:
        return None
    return got["rows"] / got["lanes"]
