"""Trace: sum of the sidecar's own spans around the engine (parse, lock_wait,
reply, send) over the fingerprint requests, per fingerprinted MB."""

import host_spans


def read(cell: dict):
    return host_spans.span_ms_per_mb(
        cell, "fdfs.sidecar.parse", "fdfs.sidecar.lock_wait",
        "fdfs.sidecar.reply", "fdfs.sidecar.send")
