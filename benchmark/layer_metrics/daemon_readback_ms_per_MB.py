"""Access log: sum of readback_us (the tmp file read back segment by
segment before each fingerprint call) over the window's uploads, per
uploaded MB."""

import host_spans


def read(cell: dict):
    return host_spans.daemon_ms_per_mb(cell, "readback_us")
