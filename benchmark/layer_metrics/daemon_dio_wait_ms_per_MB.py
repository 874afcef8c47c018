"""Access log: sum of dio_wait_us (time waited in the daemon's dio queue
before a worker took the upload) over the window's uploads, per uploaded MB."""

import host_spans


def read(cell: dict):
    return host_spans.daemon_ms_per_mb(cell, "dio_wait_us")
