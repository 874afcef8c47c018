"""Trace: share of the traced window in which the device ran nothing while at
least one request was in its handler: the sidecar's host code holds the chip."""

import host_spans


def read(cell: dict):
    return host_spans.idle_pct(cell, "sidecar")
