"""Trace: sum of fdfs.engine.slot_wait and fdfs.engine.fetch, the two places
where the host waits for the device, per fingerprinted MB."""

import host_spans


def read(cell: dict):
    return host_spans.span_ms_per_mb(cell, "fdfs.engine.slot_wait",
                                     "fdfs.engine.fetch")
