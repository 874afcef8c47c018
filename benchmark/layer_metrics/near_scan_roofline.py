"""The near-duplicate index's pass as a share of its HBM roofline.

The work is what the algorithm needs: every row the index held when a
pass began, read once (``reference_neardup.scan_bytes``; the queries and
the ranked lists are negligible beside it), summed over the window's
passes (the ``rows`` of every ``fdfs.near.scan`` span).  The least time
is those bytes over the chip's peak HBM bytes/s (``peaks.json``); the
pass is integer compare work on the VPU, for which the v5e has no
published peak, so HBM bandwidth is the only bound.  The time is the
summed device duration of the pass's jitted programs on the modules line
(``near_scan_roofline.json``).
"""

import json
import os

import reduce_trace
import reference_neardup

from . import _near

HERE = os.path.dirname(os.path.abspath(__file__))


def read(cell: dict):
    got = _near.for_cell(cell) if cell.get("trace") else None
    if not got:
        return None
    with open(os.path.join(HERE, "near_scan_roofline.json")) as fh:
        pattern = json.load(fh)["event_name_regex"]
    kernel_s = reduce_trace.seconds_matching(cell["trace"], pattern)
    if not kernel_s:
        return None
    perms = cell["config"]["widths"]["num_perms"]
    work = reference_neardup.scan_bytes(got["rows"], perms)
    peak = cell["peaks"][cell["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * (work / peak) / kernel_s
