"""Trace: sum of fdfs.near.queue_wait (a query waiting for the pass in
flight to end before its own begins) over the window's queries, in ms."""

import host_spans

from . import _near


def read(cell: dict):
    got, spans = _near.for_cell(cell), host_spans.for_cell(cell)
    if not got or not got["queries"]:
        return None
    return (spans["span_s"].get("fdfs.near.queue_wait", 0.0) * 1e3
            / got["queries"])
