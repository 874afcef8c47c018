"""Shared by the readers of the per-tile SHA-1 launches.

Every tile the engine ships opens one ``fdfs.engine.dispatch`` span that
carries the launch as arguments: ``rows`` (rows of the tile that hold a
chunk), ``lanes`` (the rows after the kernel's padding: what its rounds
run over), ``blen`` (the tile's width in bytes) and ``blocks`` (the
64-byte SHA-1 blocks the kernel walks one after another for that tile).
The sums over the traced window are read from the trace itself, through
``host_spans.load``, and kept on the cell.  A program whose spans carry
no such arguments (or a run without a trace) gives ``None``.
"""

import os

import host_spans
import reduce_trace

SPAN = "fdfs.engine.dispatch"
ARGS = ("rows", "lanes", "blocks")


def sums(trace: dict) -> dict | None:
    """{"rows", "lanes", "blocks", "tiles"} over the trace's dispatch
    spans that carry all three arguments; None when none does."""
    out = dict.fromkeys(ARGS, 0)
    tiles = 0
    for name, _, _, _, args in host_spans.host_events(trace):
        if name == SPAN and all(a in args for a in ARGS):
            tiles += 1
            for a in ARGS:
                out[a] += int(args[a])
    return {**out, "tiles": tiles} if tiles else None


def for_cell(cell: dict) -> dict | None:
    if "dispatch_sums" not in cell:
        path = cell.get("sidecar") and reduce_trace.find_xplane(
            os.path.join(cell["sidecar"].bench_dir, "trace"))
        cell["dispatch_sums"] = sums(host_spans.load(path)) if path else None
    return cell["dispatch_sums"]
