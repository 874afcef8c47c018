"""Trace + access log: share of the traced window in which the device ran
nothing, no request was open in the sidecar (``idle_no_request_pct``), and
no upload was open in the daemon at all: the node waits for its clients."""

import daemon_spans


def read(cell: dict):
    return daemon_spans.idle_pct(cell, "no_upload")
