"""Trace + access log: share of the traced window in which the device ran
nothing, no request was open in the sidecar (``idle_no_request_pct``), and
some upload was inside ``storage.fp_rpc``: its bytes are in the sidecar's
socket, or the sidecar's thread has not woken yet."""

import daemon_spans


def read(cell: dict):
    return daemon_spans.idle_pct(cell, "rpc")
