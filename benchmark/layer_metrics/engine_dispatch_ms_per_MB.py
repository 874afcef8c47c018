"""Trace: sum of fdfs.engine.dispatch (two device_put and both kernel calls,
asynchronous), per fingerprinted MB."""

import host_spans


def read(cell: dict):
    return host_spans.span_ms_per_mb(cell, "fdfs.engine.dispatch")
