"""Trace: share of the traced window in which the device ran nothing and no
request was open in the sidecar on any thread: the chip waits for the daemon."""

import host_spans


def read(cell: dict):
    return host_spans.idle_pct(cell, "no_request")
