"""Trace + access log: share of the traced window in which the device ran
nothing, no request was open in the sidecar (``idle_no_request_pct``), and
no upload was inside its RPC but some was being prepared for the chip in the
daemon: tmp read-back, chunker, lock wait, body build, negotiate, verify, present."""

import daemon_spans


def read(cell: dict):
    return daemon_spans.idle_pct(cell, "prepare")
