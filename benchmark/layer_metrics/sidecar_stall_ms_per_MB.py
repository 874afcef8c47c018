"""Trace: over the request_done markers, host_wall_us - host_cpu_us (time a
request thread spent in parse, pack, scatter and reply without the
interpreter or a core), per fingerprinted MB."""

import host_spans


def read(cell: dict):
    got = host_spans.for_cell(cell)
    if not got or not got["fingerprint_mb"]:
        return None
    return got["stall_s"] * 1e3 / got["fingerprint_mb"]
