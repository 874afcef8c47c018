"""Access log: sum of cdc_us (the native chunker, inside fp_us) over the
window's uploads, per uploaded MB."""

import host_spans


def read(cell: dict):
    return host_spans.daemon_ms_per_mb(cell, "cdc_us")
