"""A kernel's share of its HBM roofline, from the device trace.

The work is what the algorithm needs, counted from the benchmark's own
traffic: every chunk byte of the window's acknowledged chunk-eligible
uploads read once (digests of 20 B and signatures of 256 B a chunk are
negligible beside it), whatever the tiles were padded to.  The least
time is those bytes over the chip's peak HBM bytes/s (``peaks.json``);
both kernels are integer VPU work, for which the v5e has no published
peak, so HBM bandwidth is the only bound.  The kernel's time is the
summed device duration of the trace events whose names match the
pattern kept beside the metric (``<metric>.json``).
"""

import json
import os

import reduce_trace

HERE = os.path.dirname(os.path.abspath(__file__))


def algorithm_bytes(cell: dict) -> int:
    threshold = cell["config"]["widths"]["dedup_chunk_threshold"]
    return sum(u["bytes"] for u in cell["uploads"] if u["bytes"] >= threshold)


def read(cell: dict, metric: str):
    if not cell.get("trace"):
        return None
    with open(os.path.join(HERE, metric + ".json")) as fh:
        pattern = json.load(fh)["event_name_regex"]
    kernel_s = reduce_trace.seconds_matching(cell["trace"], pattern)
    work = algorithm_bytes(cell)
    if not kernel_s or not work:
        return None
    peak = cell["peaks"][cell["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * (work / peak) / kernel_s
