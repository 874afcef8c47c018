"""Trace: share of the traced window in which the device ran nothing, some
request was being received or its reply sent, and none was in its handler."""

import host_spans


def read(cell: dict):
    return host_spans.idle_pct(cell, "rpc")
