"""Trace: sum of the fdfs.sidecar.recv spans of fingerprint requests (header
read to body complete), per fingerprinted MB."""

import host_spans


def read(cell: dict):
    return host_spans.span_ms_per_mb(cell, "fdfs.sidecar.recv")
