#!/usr/bin/env python3
"""The program's own spans beside the device's operations, from one trace.

The sidecar and its engine open ``fdfs.*`` spans (``jax.profiler``
annotations: ``fastdfs_tpu/dedup/spans.py``) that land on the host plane
of the same ``.xplane.pb`` as the device's "XLA Ops" line, so both are on
one clock.  This module loads the two once per run and gives the readers
in ``layer_metrics/`` what they divide:

* the trace as plain data, ``{"planes": [{"name", "lines": [{"name",
  "events": [[name, start_ns, duration_ns, {argument: value}], ...]}]}]}``
  (:func:`load`; only the device's operation and module lines and the
  host's ``fdfs.*`` events are kept), so that the arithmetic can be checked
  on a hand-made fixture (``tests/test_host_spans.py``);
* :func:`summarize`: seconds and count by span name over the fingerprint
  requests (the count times a span's own cost is what a traced run's
  numbers hold of the instrumentation), the fingerprinted MB and the stall
  of the ``request_done`` markers, and the device's idle time put down to
  what the host was doing;
* :func:`name_gaps`: the longest idle gaps, each with the state that
  covered most of it and, where the sidecar held the chip, the span;
* :func:`late_columns`: the access log's columns after ``req_bytes``.

A program without spans (or a log without the columns) gives ``None``
everywhere, and the readers then leave their metric out of the line.

By hand, on a trace that was kept::

    python3 benchmark/host_spans.py <file.xplane.pb>

prints the summary, the ten longest gaps by name, each compiled program's
share of the device's busy time with the operations it owns, and the share
of ``fdfs.engine.fetch`` spans that contain the end of a device operation
(the check that the clocks are one).
"""

from __future__ import annotations

import bisect
import contextlib
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reduce_trace  # noqa: E402

PREFIX = "fdfs."
FINGERPRINT_CMDS = (120, 125)      # DEDUP_FINGERPRINT, DEDUP_FINGERPRINT_CUTS
ROOT = "fdfs.sidecar.request"
WIRE = ("fdfs.sidecar.recv", "fdfs.sidecar.send")
MARKER = "fdfs.sidecar.request_done"
# Spans that hold others on their thread: a gap is named by the innermost
# span, and by a holder only for the time none of its children covers.
CHILDREN = {
    ROOT: ("fdfs.sidecar.parse", "fdfs.engine.fingerprint",
           "fdfs.sidecar.lock_wait", "fdfs.sidecar.reply",
           "fdfs.sidecar.verify"),
    "fdfs.engine.fingerprint": (
        "fdfs.engine.slot_wait", "fdfs.engine.pack", "fdfs.engine.dispatch",
        "fdfs.engine.fetch", "fdfs.engine.scatter")}
STATES = ("no_request", "rpc", "sidecar")


def _rules() -> dict:
    with open(os.path.join(HERE, "trace.json")) as fh:
        return json.load(fh)


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    rules = _rules()
    device = re.compile(rules["device_plane_regex"])
    kept = (rules["ops_line"], rules["modules_line"])
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        on_device = bool(device.search(plane.name))
        for line in plane.lines:
            if on_device:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns), {}]
                          for ev in line.events] if line.name in kept else []
            else:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           dict(ev.stats)]
                          for ev in line.events if ev.name.startswith(PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def host_events(trace: dict) -> list[tuple]:
    """(name, thread, start_ns, end_ns, arguments) of every fdfs.* span; a
    thread is a line of a plane that is no device."""
    device = re.compile(_rules()["device_plane_regex"])
    out, thread = [], 0
    for plane in trace["planes"]:
        if device.search(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur, args in line["events"]:
                if name.startswith(PREFIX):
                    out.append((name, thread, start, start + dur, args))
            thread += 1
    return out


def device_lines(trace: dict) -> list[tuple[list, list]]:
    """Per device plane: (its operations, its program runs), each as
    [name, start_ns, end_ns]."""
    rules = _rules()
    device = re.compile(rules["device_plane_regex"])
    out = []
    for plane in trace["planes"]:
        if not device.search(plane["name"]):
            continue
        by_line = {line["name"]: [[n, s, s + d] for n, s, d, _ in
                                  line["events"]] for line in plane["lines"]}
        if by_line.get(rules["ops_line"]):
            out.append((by_line[rules["ops_line"]],
                        by_line.get(rules["modules_line"], [])))
    return out


def _merged(intervals) -> list[list[int]]:
    return reduce_trace.union_seconds(list(intervals))[1]


def idle_by_state(busy: list[list[int]], spans: list[tuple],
                  start: int, end: int) -> dict[str, float]:
    """Seconds of [start, end) in which the device ran nothing, by what the
    host was doing: ``sidecar`` while some request was between handler
    entry and reply built (its root span was open on any thread), ``rpc``
    while none was but some request was being received or its reply
    sent, ``no_request`` otherwise.  One sweep over the interval ends."""
    points = [(t, 0, d) for s, e in busy for t, d in ((s, 1), (e, -1))]
    for name, _, s, e, _ in spans:
        if name == ROOT or name in WIRE:
            kind = 1 if name == ROOT else 2
            points += [(s, kind, 1), (e, kind, -1)]
    points.sort()
    out = dict.fromkeys(STATES, 0)
    depth, prev = [0, 0, 0], start
    for t, kind, step in points + [(end, 0, 0)]:
        t = min(max(t, start), end)
        if t > prev and not depth[0]:
            out["sidecar" if depth[1] else "rpc" if depth[2]
                else "no_request"] += t - prev
        prev = max(prev, t)
        depth[kind] += step
    return {k: v / 1e9 for k, v in out.items()}


def summarize(trace: dict) -> dict | None:
    """None when the program wrote no span into the trace."""
    spans = host_events(trace)
    if not spans:
        return None
    span_s: dict[str, float] = {}
    span_n: dict[str, int] = {}
    mb = stall_s = 0.0
    markers = 0
    for name, _, start, end, args in spans:
        if name == MARKER:
            markers += 1
            mb += args.get("bytes", 0) / 1e6
            stall_s += (args.get("host_wall_us", 0)
                        - args.get("host_cpu_us", 0)) / 1e6
        elif name in WIRE + (ROOT,) and args.get("cmd") not in FINGERPRINT_CMDS:
            continue        # commit, query, stats...: not the fingerprint RPC
        else:
            span_s[name] = span_s.get(name, 0.0) + (end - start) / 1e9
            span_n[name] = span_n.get(name, 0) + 1
    out = {"span_s": span_s, "span_n": span_n, "fingerprint_mb": mb,
           "stall_s": stall_s, "requests": markers, "idle_s": None,
           "extent_s": 0.0}
    idle = []
    for ops, _ in device_lines(trace):
        busy = _merged((s, e) for _, s, e in ops)
        first = min([busy[0][0]] + [s for _, _, s, _, _ in spans])
        last = max([busy[-1][1]] + [e for _, _, _, e, _ in spans])
        idle.append(idle_by_state(busy, spans, first, last))
        out["extent_s"] = (last - first) / 1e9
    if idle:
        out["idle_s"] = {k: sum(d[k] for d in idle) / len(idle)
                         for k in STATES}
    return out


def name_gaps(trace: dict, n: int = 10) -> list[dict]:
    """The n longest gaps between device operations (first device), each
    with the share of it every state covered and the state that covered
    most; for ``sidecar`` also the innermost span that covered most of the
    gap (a holder such as ``fdfs.engine.fingerprint`` only for what its
    children leave)."""
    spans, planes = host_events(trace), device_lines(trace)
    if not spans or not planes:
        return []
    busy = _merged((s, e) for _, s, e in planes[0][0])
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:n]
    out = []
    for length, g0, g1 in gaps:
        near = [sp for sp in spans if sp[3] > g0 and sp[2] < g1]
        states = idle_by_state([], near, g0, g1)
        state = max(states, key=states.get)
        row = {"gap_s": length / 1e9, "at_s": (g0 - busy[0][0]) / 1e9,
               "state": "idle_" + state,
               "shares": {k: v * 1e9 / length for k, v in states.items()}}
        if state == "sidecar":
            inside: dict[str, int] = {}
            for name, _, s, e, _ in near:    # what ran under some root
                if name != MARKER and name not in WIRE:
                    inside[name] = (inside.get(name, 0)
                                    + min(e, g1) - max(s, g0))
            own = dict(inside)
            for holder, children in CHILDREN.items():
                if holder in inside:
                    own[holder] -= sum(inside.get(c, 0) for c in children)
            row["span"] = max(own, key=own.get)
            row["span_share"] = own[row["span"]] / length
        out.append(row)
    return out


def fetch_holds_device_end(trace: dict) -> tuple[int, int]:
    """(fetch spans that contain the end of a device operation, fetch
    spans): a fetch returns when the device has finished, so if the two
    clocks are one, nearly every fetch span holds such an end."""
    ends = sorted(e for ops, _ in device_lines(trace) for _, _, e in ops)
    fetches = [(s, e) for n, _, s, e, _ in host_events(trace)
               if n == "fdfs.engine.fetch"]
    held = sum(bisect.bisect_right(ends, e) > bisect.bisect_left(ends, s)
               for s, e in fetches)
    return held, len(fetches)


def programs(trace: dict) -> dict:
    """Each compiled program's seconds on the device (first device) and the
    operations that ran inside its runs: {program: {"s", "ops": {op: s}}}."""
    planes = device_lines(trace)
    if not planes:
        return {}
    ops, runs = planes[0]
    # a run's id "(...)" is taken off its program's name, as reduce_trace does
    runs = sorted(([re.sub(r"\(\d+\)$", "", n), s, e] for n, s, e in runs),
                  key=lambda r: r[1])
    starts = [r[1] for r in runs]
    out: dict[str, dict] = {}
    for name, s, e in runs:
        out.setdefault(name, {"s": 0.0, "ops": {}})["s"] += (e - s) / 1e9
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        owner = runs[i][0] if i >= 0 and s < runs[i][2] else "(no program)"
        into = out.setdefault(owner, {"s": 0.0, "ops": {}})["ops"]
        name = name.split(" = ", 1)[0]
        into[name] = into.get(name, 0.0) + (e - s) / 1e9
    return out


# -- what the readers call ---------------------------------------------------------

def for_cell(cell: dict) -> dict | None:
    """The run's summary, parsed from the xplane once and kept on the cell."""
    if "host_spans" not in cell:
        path = reduce_trace.find_xplane(
            os.path.join(cell["sidecar"].bench_dir, "trace"))
        cell["host_spans"] = summarize(load(path)) if path else None
    return cell["host_spans"]


def span_ms_per_mb(cell: dict, *names: str):
    got = for_cell(cell)
    if not got or not got["fingerprint_mb"]:
        return None
    return (sum(got["span_s"].get(n, 0.0) for n in names) * 1e3
            / got["fingerprint_mb"])


def idle_pct(cell: dict, state: str):
    got = for_cell(cell)
    if not got or not got["idle_s"] or not cell.get("trace_window_s"):
        return None
    idle_s = got["idle_s"][state]
    if state == "no_request":
        # The window's ends beyond the trace's first and last event (the
        # callers' start, their drain) hold no operation and no span.
        idle_s += max(0.0, cell["trace_window_s"] - got["extent_s"])
    return 100.0 * idle_s / cell["trace_window_s"]


LATE = ("cdc_us", "dio_wait_us", "readback_us")


def late_columns(cell: dict) -> list[dict] | None:
    """The window's acknowledged upload rows of the daemon's access log
    with the columns that follow ``req_bytes``; None where the log has
    none (a daemon from before them)."""
    if "late_columns" not in cell:
        run_dir = os.path.dirname(os.path.dirname(cell["sidecar"].bench_dir))
        rows = []
        with contextlib.suppress(FileNotFoundError), open(os.path.join(
                run_dir, "st", "logs", "access.log")) as fh:
            for line in fh:
                f = line.split()
                if len(f) >= 13 and not f[0].startswith("{") and f[2] == "11":
                    rows.append(f)
        rows = [f for f in rows[cell["preloaded_files"]:] if f[3] == "0"]
        cell["late_columns"] = None if not rows or any(
            len(f) < 16 for f in rows) else [
            dict(zip(("req_bytes",) + LATE, map(int, f[12:16]))) for f in rows]
    return cell["late_columns"]


def daemon_ms_per_mb(cell: dict, column: str):
    rows = late_columns(cell)
    mb = sum(r["req_bytes"] for r in rows or ()) / 1e6
    return sum(r[column] for r in rows) / 1e3 / mb if mb else None


def main(argv: list[str]) -> int:
    trace = load(argv[0])
    by_program = programs(trace)
    total = sum(p["s"] for p in by_program.values())
    print(json.dumps({
        "summary": summarize(trace), "gaps": name_gaps(trace),
        "fetch_spans_holding_a_device_end": fetch_holds_device_end(trace),
        "programs": {k: {"share": v["s"] / total if total else 0, **v}
                     for k, v in by_program.items()}}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
