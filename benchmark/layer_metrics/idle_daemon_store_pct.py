"""Trace + access log: share of the traced window in which the device ran
nothing, no request was open in the sidecar (``idle_no_request_pct``), and
every open upload was past the chip: chunk-store writes, binlog, the reply."""

import daemon_spans


def read(cell: dict):
    return daemon_spans.idle_pct(cell, "store")
