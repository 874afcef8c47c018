"""Shared by the readers of the near-duplicate index.

Every pass of the index opens one ``fdfs.near.scan`` span that carries
the pass as arguments: ``queries`` (how many it answers) and ``rows`` (the
rows the index held when it began: what the pass has to read).  The sums
over the traced window are read from the trace itself, through
``host_spans.load``, and kept on the cell: each span is one increment of
the sidecar's ``near_scans`` counter and its ``queries`` one of
``near_queries`` (``run.py`` keeps no ``stats`` snapshot but the keys of
``sidecar_delta``, so the counters' increments are taken where they are
stamped).  A program without the span, or a run without a trace, gives
``None``.
"""

import os

import host_spans
import reduce_trace

SPAN = "fdfs.near.scan"


def sums(trace: dict) -> dict | None:
    """{"scans", "queries", "rows", "scan_s"} over the trace's scan spans
    that carry both arguments; None when none does."""
    scans = queries = rows = ns = 0
    for name, _, start, end, args in host_spans.host_events(trace):
        if name == SPAN and "queries" in args and "rows" in args:
            scans += 1
            queries += int(args["queries"])
            rows += int(args["rows"])
            ns += end - start
    return {"scans": scans, "queries": queries, "rows": rows,
            "scan_s": ns / 1e9} if scans else None


def for_cell(cell: dict) -> dict | None:
    if "near_sums" not in cell:
        path = cell.get("sidecar") and reduce_trace.find_xplane(
            os.path.join(cell["sidecar"].bench_dir, "trace"))
        cell["near_sums"] = sums(host_spans.load(path)) if path else None
    return cell["near_sums"]


def span_mean_ms(cell: dict, name: str):
    """Mean wall time of the window's ``name`` spans, in ms."""
    got = host_spans.for_cell(cell)
    n = got["span_n"].get(name) if got else None
    return got["span_s"][name] * 1e3 / n if n else None
