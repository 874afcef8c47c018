#!/usr/bin/env python3
"""The storage daemon's stages beside the device's operations, on one clock.

With ``use_access_log`` the daemon writes every request's stage intervals
after its column row (``{"event":"stages",...}``: ``native/common/trace.cc:
StageLineJson``), stamped with the host's ``CLOCK_MONOTONIC``.  The sidecar's
``fdfs.sidecar.request_done`` markers carry the same clock's reading
(``mono_us``) into the ``.xplane.pb``, so each marker is an anchor: its time
in the trace minus its ``mono_us`` is the offset between the two clocks.
This module moves the daemon's intervals onto the trace's clock by the
median anchor and sweeps them together with what ``host_spans`` reads:

* :func:`idle_by_daemon_state`: the time in which the device ran nothing
  and no request was open in the sidecar on any thread (what
  ``idle_no_request_pct`` counts) split by what the daemon was doing.
  Requests of commands 11, 132 and 133 are uploads; the state of an instant
  is that of the upload furthest along this order: ``rpc`` (inside
  ``storage.fp_rpc``: the bytes are in the socket, or the sidecar's thread
  has not woken), ``prepare`` (``storage.tmp_readback``,
  ``storage.fingerprint`` or ``storage.reindex`` outside their RPC,
  ``storage.negotiate``, ``storage.commit.verify``,
  ``storage.commit.present``), ``recv`` (``storage.recv``,
  ``dio.queue_wait``), ``store`` (the rest of an open upload: chunk-store
  writes, binlog, the reply), ``no_upload`` (none is open: the node waits
  for its clients);
* :func:`clock_match`: of the window's ``fdfs.sidecar.request`` spans of
  the fingerprint opcodes, those that lie wholly inside the daemon's
  ``storage.fp_rpc`` of the same ``session`` and ``base_offset``: the proof
  that the two programs are on one clock;
* :func:`name_gaps`: ``host_spans.name_gaps`` with, beside each gap, the
  daemon's states' shares of it and the daemon span that covered most.

A log without stage lines or a trace without anchors (the parent of the PR
that added them) gives ``None``, and the readers leave their metric out.

By hand, on a trace and a log that were kept::

    python3 benchmark/daemon_spans.py <file.xplane.pb> <access.log>
"""

from __future__ import annotations

import contextlib
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import host_spans  # noqa: E402
import reduce_trace  # noqa: E402

UPLOAD_CMDS = (11, 132, 133)    # UPLOAD_FILE, UPLOAD_RECIPE, UPLOAD_CHUNKS
RPC = "storage.fp_rpc"
PREPARE = ("storage.tmp_readback", "storage.fingerprint", "storage.reindex",
           "storage.negotiate", "storage.commit.verify",
           "storage.commit.present")
RECV = ("storage.recv", "dio.queue_wait")
STATES = ("rpc", "prepare", "recv", "store", "no_upload")
MAX_TOLERANCE_NS = 200_000


def stage_lines(access_log: str) -> list[dict]:
    """The log's stage lines, in file order; [] without the file."""
    out = []
    with contextlib.suppress(FileNotFoundError), open(access_log) as fh:
        for line in fh:
            if line.startswith('{"event":"stages"'):
                with contextlib.suppress(ValueError):
                    out.append(json.loads(line))
    return out


def anchors(trace: dict) -> dict | None:
    """The trace's clock minus the host's CLOCK_MONOTONIC, in ns, over the
    markers that carry ``mono_us``: the median, and the spread between
    their first and ninth decile."""
    offsets = sorted(start - args["mono_us"] * 1000
                     for name, _, start, _, args in host_spans.host_events(trace)
                     if name == host_spans.MARKER and "mono_us" in args)
    if not offsets:
        return None
    lo = offsets[len(offsets) // 10]
    hi = offsets[len(offsets) - 1 - len(offsets) // 10]
    return {"offset_ns": int(statistics.median(offsets)),
            "spread_ns": hi - lo, "anchors": len(offsets)}


def on_trace_clock(lines: list[dict], offset_ns: int) -> list[dict]:
    """Each logged request with its ends and its intervals in the trace's
    ns: {"cmd", "start", "end", "spans": [(name, start, end, parent,
    arguments)]}."""
    out = []
    for rec in lines:
        t0 = rec["t0_mono_us"] * 1000 + offset_ns
        out.append({
            "cmd": rec["cmd"], "start": t0, "end": t0 + rec["dur_us"] * 1000,
            "spans": [(sp[0], t0 + sp[1] * 1000, t0 + (sp[1] + sp[2]) * 1000,
                       sp[3], sp[4] if len(sp) > 4 else {})
                      for sp in rec["spans"]]})
    return out


def _points(requests: list[dict]) -> list[tuple]:
    """Interval ends of the uploads by kind: 3 = inside the RPC, 4 = a
    prepare span, 5 = a receive span, 6 = the upload is open."""
    points = []
    for req in requests:
        if req["cmd"] not in UPLOAD_CMDS:
            continue
        points += [(req["start"], 6, 1), (req["end"], 6, -1)]
        for name, s, e, _, _ in req["spans"]:
            kind = (3 if name == RPC else 4 if name in PREPARE
                    else 5 if name in RECV else None)
            if kind:
                points += [(s, kind, 1), (e, kind, -1)]
    return points


def idle_by_daemon_state(busy: list, spans: list[tuple],
                         requests: list[dict], start: int,
                         end: int) -> dict[str, float]:
    """Seconds of [start, end) in which the device ran nothing and no
    ``fdfs.sidecar.recv`` / ``request`` / ``send`` was open, by the state
    of the upload furthest along STATES.  One sweep over the interval
    ends, as ``host_spans.idle_by_state`` makes it."""
    points = [(t, 0, d) for s, e in busy for t, d in ((s, 1), (e, -1))]
    for name, _, s, e, _ in spans:
        if name == host_spans.ROOT or name in host_spans.WIRE:
            points += [(s, 1, 1), (e, 1, -1)]
    points += _points(requests)
    points.sort()
    out = dict.fromkeys(STATES, 0)
    depth, prev = [0] * 7, start
    for t, kind, step in points + [(end, 0, 0)]:
        t = min(max(t, start), end)
        if t > prev and not depth[0] and not depth[1]:
            out["rpc" if depth[3] else "prepare" if depth[4]
                else "recv" if depth[5] else "store" if depth[6]
                else "no_upload"] += t - prev
        prev = max(prev, t)
        depth[kind] += step
    return {k: v / 1e9 for k, v in out.items()}


def clock_match(spans: list[tuple], requests: list[dict],
                tolerance_ns: int) -> tuple[int, int]:
    """(sidecar fingerprint request spans wholly inside the daemon's
    ``storage.fp_rpc`` of the same session and base_offset, such spans)."""
    rpcs: dict[tuple, list] = {}
    for req in requests:
        for name, s, e, _, args in req["spans"]:
            if name == RPC:
                rpcs.setdefault((args.get("session"), args.get("base_offset")),
                                []).append((s, e))
    held = total = 0
    for name, _, s, e, args in spans:
        if name != host_spans.ROOT or \
                args.get("cmd") not in host_spans.FINGERPRINT_CMDS:
            continue
        total += 1
        key = (args.get("session"), args.get("base_offset"))
        held += any(rs - tolerance_ns <= s and e <= re + tolerance_ns
                    for rs, re in rpcs.get(key, ()))
    return held, total


def _extents(trace: dict, spans: list[tuple]):
    """Per device plane: (merged busy intervals, first, last), the extent
    ``host_spans.summarize`` sweeps."""
    for ops, _ in host_spans.device_lines(trace):
        busy = reduce_trace.union_seconds([(s, e) for _, s, e in ops])[1]
        yield (busy, min([busy[0][0]] + [s for _, _, s, _, _ in spans]),
               max([busy[-1][1]] + [e for _, _, _, e, _ in spans]))


def summarize(trace: dict, lines: list[dict], window_s: float | None = None,
              last_done_mono_s: float | None = None) -> dict | None:
    """None without stage lines or anchors.  ``window_s``: the traced
    window, whose ends beyond the trace's first and last event are swept
    too (no operation and no sidecar span lies there); of the two, the
    end after the last event reaches to ``last_done_mono_s`` (the last
    operation's completion on CLOCK_MONOTONIC), the rest lies before the
    first."""
    clock = anchors(trace)
    if not lines or clock is None:
        return None
    spans = host_spans.host_events(trace)
    requests = on_trace_clock(lines, clock["offset_ns"])
    tolerance = min(clock["spread_ns"], MAX_TOLERANCE_NS)
    held, total = clock_match(spans, requests, tolerance)
    # Which clock the profiler stamps with: the log has both of the
    # host's.  "session" = neither: the trace counts from its own start.
    wall_minus_mono = statistics.median(
        r["t0_wall_us"] - r["t0_mono_us"] for r in lines) * 1000
    trace_clock = ("monotonic" if abs(clock["offset_ns"]) < 1e9
                   else "realtime"
                   if abs(clock["offset_ns"] - wall_minus_mono) < 1e9
                   else "session")
    out = {**clock, "tolerance_ns": tolerance, "trace_clock": trace_clock,
           "rpc_spans": total, "rpc_spans_matched": held,
           "uploads": sum(r["cmd"] in UPLOAD_CMDS for r in lines),
           "idle_s": None, "swept_s": 0.0}
    idle = []
    for busy, first, last in _extents(trace, spans):
        ends = (max(0.0, window_s - (last - first) / 1e9) * 1e9
                if window_s else 0.0)
        tail = ends if last_done_mono_s is None else min(ends, max(
            0.0, last_done_mono_s * 1e9 + clock["offset_ns"] - last))
        start, end = int(first - (ends - tail)), int(last + tail)
        idle.append(idle_by_daemon_state(busy, spans, requests, start, end))
        out["swept_s"] = (end - start) / 1e9
    if idle:
        out["idle_s"] = {k: sum(d[k] for d in idle) / len(idle)
                         for k in STATES}
    return out


def name_gaps(trace: dict, lines: list[dict], n: int = 10) -> list[dict]:
    """``host_spans.name_gaps`` with, for each gap, ``daemon_shares`` (the
    five states' shares of the gap: they cover what ``no_request`` does)
    and ``daemon_span``: the upload stage that covered most of the gap,
    a holder only for what its children leave, ``request.rest`` for an
    open upload outside every stage."""
    clock, rows = anchors(trace), host_spans.name_gaps(trace, n)
    planes = host_spans.device_lines(trace)
    if not lines or clock is None or not rows or not planes:
        return rows
    spans = host_spans.host_events(trace)
    requests = on_trace_clock(lines, clock["offset_ns"])
    t_first = min(s for _, s, _ in planes[0][0])
    for row in rows:
        g0 = t_first + int(round(row["at_s"] * 1e9))
        g1 = g0 + int(round(row["gap_s"] * 1e9))
        near_spans = [sp for sp in spans if sp[3] > g0 and sp[2] < g1]
        near = [r for r in requests if r["end"] > g0 and r["start"] < g1]
        states = idle_by_daemon_state([], near_spans, near, g0, g1)
        row["daemon_shares"] = {k: v * 1e9 / (g1 - g0)
                                for k, v in states.items()}
        own: dict[str, int] = {}
        for req in near:
            if req["cmd"] not in UPLOAD_CMDS:
                continue
            cover = [max(0, min(e, g1) - max(s, g0))
                     for _, s, e, _, _ in req["spans"]]
            rest = max(0, min(req["end"], g1) - max(req["start"], g0))
            left = list(cover)
            for i, (_, _, _, parent, _) in enumerate(req["spans"]):
                if parent >= 0:
                    left[parent] -= cover[i]
                else:
                    rest -= cover[i]
            for (name, *_), ns in zip(req["spans"], left):
                own[name] = own.get(name, 0) + ns
            own["request.rest"] = own.get("request.rest", 0) + rest
        if own:
            row["daemon_span"] = max(own, key=own.get)
            row["daemon_span_share"] = own[row["daemon_span"]] / (g1 - g0)
    return rows


# -- what the readers call ---------------------------------------------------------

def for_cell(cell: dict) -> dict | None:
    """The run's summary, made once and kept on the cell."""
    if "daemon_spans" not in cell:
        bench_dir = cell["sidecar"].bench_dir
        path = reduce_trace.find_xplane(os.path.join(bench_dir, "trace"))
        run_dir = os.path.dirname(os.path.dirname(bench_dir))
        lines = stage_lines(os.path.join(run_dir, "st", "logs", "access.log"))
        done = [op["t_done"] for op in cell.get("ops") or ()]
        cell["daemon_spans"] = summarize(
            host_spans.load(path), lines, cell.get("trace_window_s"),
            max(done) if done else None) if path and lines else None
    return cell["daemon_spans"]


def idle_pct(cell: dict, state: str):
    got = for_cell(cell)
    if not got or not got["idle_s"] or not cell.get("trace_window_s"):
        return None
    return 100.0 * got["idle_s"][state] / cell["trace_window_s"]


def clock_match_pct(cell: dict):
    got = for_cell(cell)
    if not got or not got["rpc_spans"]:
        return None
    return 100.0 * got["rpc_spans_matched"] / got["rpc_spans"]


def main(argv: list[str]) -> int:
    trace, lines = host_spans.load(argv[0]), stage_lines(argv[1])
    got = summarize(trace, lines)
    if got and got["idle_s"]:
        got["idle_pct_of_extent"] = {
            k: 100.0 * v / got["swept_s"] for k, v in got["idle_s"].items()}
    print(json.dumps({"summary": got, "gaps": name_gaps(trace, lines)},
                     indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
