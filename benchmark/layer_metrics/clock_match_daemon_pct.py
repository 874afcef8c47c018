"""Trace + access log: of the window's ``fdfs.sidecar.request`` spans of the
fingerprint opcodes, the share that lies wholly inside the daemon's
``storage.fp_rpc`` of the same session and base_offset, once the daemon's
CLOCK_MONOTONIC stamps are moved onto the trace's clock by the markers."""

import daemon_spans


def read(cell: dict):
    return daemon_spans.clock_match_pct(cell)
