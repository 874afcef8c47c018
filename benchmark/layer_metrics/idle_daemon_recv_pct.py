"""Trace + access log: share of the traced window in which the device ran
nothing, no request was open in the sidecar (``idle_no_request_pct``), and
no upload was further than its receive: ``storage.recv`` or ``dio.queue_wait``."""

import daemon_spans


def read(cell: dict):
    return daemon_spans.idle_pct(cell, "recv")
