"""Trace: sum of fdfs.engine.pack (zero-fill and row copies into the staging
tile) and fdfs.engine.scatter (rows copied out), per fingerprinted MB."""

import host_spans


def read(cell: dict):
    return host_spans.span_ms_per_mb(cell, "fdfs.engine.pack",
                                     "fdfs.engine.scatter")
