#!/usr/bin/env python
"""Aggregate the storage daemon's per-stage access log into a stage table.

The daemon (storage.conf:use_access_log) writes one line per request to
``<base_path>/logs/access.log``:

    <epoch> <ip> <cmd> <status> <bytes> <cost_us> <recv_us> <work_us>
    <fp_us> <fp_lock_us> <cswrite_us> <binlog_us> <req_bytes>
    <cdc_us> <dio_wait_us> <readback_us>
    <negotiate_us> <present_us> <verify_us> <recipe_us> <reindex_us>

(native/storage/server.cc:LogAccess; older 8-, 13- and 16-column logs
parse too, with zeros for the stages they lack).  The last five are the
negotiated upload's: ``negotiate_us`` is an ``upload_recipe`` request's
parse + pin-and-mask; an ``upload_chunks`` commit assembles the file
segment by segment in its worker's buffer, and its columns are sums over
the segments: ``verify_us`` (shipped chunks: read into place, digest
check, write), ``present_us`` (chunks the store had: referenced, read
into place by one batched read a segment, then the CRC over the whole
assembled segment) and ``recipe_us`` make up ``cswrite_us``;
``reindex_us`` is the assembled segment cut and fingerprinted for the
file's signature, the answer held against the client's recipe, and the
fingerprint session's commit, all before the reply (0 in cpu mode).
``cdc_us`` is the native
chunker's share of ``fp_us`` and ``fp_lock_us`` the wait for a sidecar
connection inside it (on an ``upload_chunks`` row, which has no ``fp_us``,
both are shares of ``reindex_us``); ``dio_wait_us`` (the wait in the dio
queue) and ``readback_us`` (the tmp file read back before each fingerprint
call) lie inside ``work_us``.  Every stage column is the sum of the
request's intervals of that stage; the intervals themselves follow the row
as one compact-JSON line,

    {"event":"stages","cmd":...,"status":...,"t0_mono_us":...,
     "t0_wall_us":...,"dur_us":...,"truncated":0,
     "spans":[[name,start_offset_us,dur_us,parent_index(,{args})],...]}

which ``aggregate`` skips like the slow-request line and ``--timeline N``
draws for the N slowest requests (``fastdfs_tpu.trace.logged_requests`` +
``render_timeline``; OPERATIONS.md, "Tracing").  This tool answers the question the raw ingest rate
can't: WHERE does an upload's time go — network receive, fingerprinting
(and how much of that is queueing on the sidecar's serialized engine),
chunk-store writes, or the binlog — the attribution SURVEY.md §3.1 marks
on the reference's ``dio_write_file()`` hot loop.

The daemon's slow-request gate (storage.conf:slow_request_threshold_ms)
additionally interleaves one compact-JSON line per slow request:

    {"event":"slow_request","role":"storage","op":...,"trace_id":...,
     "span_id":...,"start_us":...,"dur_us":...,"status":...,"peer":...,
     "bytes":...}

``aggregate`` skips both kinds (a compact JSON line is a single token);
``slow_requests`` ingests them, and ``--slow`` renders them with the
``cli.py trace --trace-id`` command that drills into each one.

Usage:  python tools/access_log_stages.py <access.log> [--json] [--slow]
                                          [--timeline N]
Import: ``aggregate(path) -> dict``; ``slow_requests(path) -> list[dict]``.
"""

from __future__ import annotations

import argparse
import json
import sys

CMD_NAMES = {
    11: "upload", 12: "delete", 14: "download", 16: "sync_create",
    21: "upload_slave", 22: "query_info", 23: "upload_appender",
    24: "append", 26: "fetch_binlog", 34: "modify", 36: "truncate",
    124: "near_dups", 126: "sync_query_chunks", 127: "sync_recipe",
    128: "fetch_recipe", 129: "fetch_chunk", 132: "upload_recipe",
    133: "upload_chunks", 149: "query_chunking",
}

STAGES = ["recv_us", "work_us", "fp_us", "fp_lock_us", "cswrite_us",
          "binlog_us"]
# appended after req_bytes, so they follow it in a line
LATE_STAGES = ["cdc_us", "dio_wait_us", "readback_us"]
# the negotiated upload's own stages, after those
INGEST_STAGES = ["negotiate_us", "present_us", "verify_us", "recipe_us",
                 "reindex_us"]
ALL_STAGES = STAGES + LATE_STAGES + INGEST_STAGES


def _pct(sorted_vals: list[int], q: float) -> int:
    if not sorted_vals:
        return 0
    i = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[i]


def slow_requests(path: str) -> list[dict]:
    """The structured slow-request JSON lines, in file order.  Malformed
    or non-slow JSON lines are skipped (the log interleaves formats)."""
    out: list[dict] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and rec.get("event") == "slow_request":
                out.append(rec)
    return out


def aggregate(path: str) -> dict:
    """Per-command stage totals, means, and latency percentiles.
    Slow-request JSON lines are ignored here (see ``slow_requests``)."""
    per_cmd: dict[int, dict] = {}
    with open(path) as fh:
        for line in fh:
            f = line.split()
            if len(f) < 8 or f[0].startswith("{"):
                continue
            try:
                cmd, status = int(f[2]), int(f[3])
                nums = [int(x) for x in f[4:21]]
            except ValueError:
                continue
            nums += [0] * (17 - len(nums))  # older column counts
            bytes_, cost = nums[0], nums[1]
            stages = nums[2:8] + nums[9:17]
            req_bytes = nums[8]
            d = per_cmd.setdefault(cmd, {
                "count": 0, "errors": 0, "bytes": 0, "req_bytes": 0,
                "cost_us": [], **{s: 0 for s in ALL_STAGES}})
            d["count"] += 1
            d["errors"] += status != 0
            d["bytes"] += bytes_
            d["req_bytes"] += req_bytes
            d["cost_us"].append(cost)
            for name, v in zip(ALL_STAGES, stages):
                d[name] += v
    out = {}
    for cmd, d in sorted(per_cmd.items()):
        costs = sorted(d.pop("cost_us"))
        total_cost = sum(costs)
        n = d["count"]
        row = {
            "count": n, "errors": d["errors"], "bytes": d["bytes"],
            "req_bytes": d["req_bytes"],
            "total_cost_s": round(total_cost / 1e6, 3),
            "mean_us": total_cost // max(n, 1),
            "p50_us": _pct(costs, 0.50),
            "p95_us": _pct(costs, 0.95),
            "p99_us": _pct(costs, 0.99),
            "stages_s": {s: round(d[s] / 1e6, 3) for s in ALL_STAGES},
            # share of total request time per stage ("other" = dispatch,
            # response send, file-id mint, rename, ...)
            "stage_share": {},
        }
        if total_cost > 0:
            # fp_lock and cdc are subsets of fp (of reindex on a commit,
            # which has no fp); work contains dio_wait +
            # readback + fp + cswrite + binlog + negotiate + reindex;
            # cswrite contains present + verify + recipe (of a negotiated
            # commit it is their sum, so cs_write below is recipe_us).
            # Report the orthogonal decomposition of cost_us.
            recv = d["recv_us"]
            fp = d["fp_us"]
            lock = d["fp_lock_us"]
            cs = d["cswrite_us"]
            bl = d["binlog_us"]
            cdc = d["cdc_us"]
            wait = d["dio_wait_us"]
            rb = d["readback_us"]
            neg, ri = d["negotiate_us"], d["reindex_us"]
            present, verify = d["present_us"], d["verify_us"]
            in_reindex = 0 if fp else cdc + lock
            other_work = max(
                d["work_us"] - fp - cs - bl - wait - rb - neg - ri, 0)
            pre = max(total_cost - d["recv_us"] - d["work_us"], 0)
            for name, v in [("recv", recv), ("dio_wait", wait),
                            ("tmp_readback", rb), ("fp_cdc", cdc),
                            ("fp_rpc", max(fp - lock - cdc, 0)),
                            ("fp_lock_wait", lock),
                            ("negotiate", neg),
                            ("commit_present", present),
                            ("commit_verify", verify),
                            ("cs_write", cs - present - verify),
                            ("reindex", max(ri - in_reindex, 0)),
                            ("binlog", bl), ("work_other", other_work),
                            ("dispatch_other", pre)]:
                row["stage_share"][name] = round(v / total_cost, 4)
        out[CMD_NAMES.get(cmd, f"cmd{cmd}")] = row
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("log", help="path to access.log")
    ap.add_argument("--json", action="store_true", help="raw JSON output")
    ap.add_argument("--slow", action="store_true",
                    help="show the structured slow-request lines instead")
    ap.add_argument("--timeline", type=int, metavar="N", default=0,
                    help="draw the N slowest requests that logged stages")
    args = ap.parse_args()
    if args.timeline:
        import os
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from fastdfs_tpu import trace as T
        spans = T.logged_requests(args.log, cmd_names=CMD_NAMES)
        roots = sorted((s for s in spans if s.parent_id == 0),
                       key=lambda s: -s.dur_us)[:args.timeline]
        for root in roots:
            print(T.render_timeline(spans, root.trace_id))
        if not roots:
            print("no stage lines (use_access_log on a daemon that "
                  "writes them?)")
        return 0
    if args.slow:
        slow = slow_requests(args.log)
        if args.json:
            json.dump(slow, sys.stdout, indent=2)
            print()
            return 0
        for rec in slow:
            print(f"{rec.get('role', '?')} {rec.get('op', '?')} "
                  f"dur={rec.get('dur_us', 0) / 1000:.1f}ms "
                  f"status={rec.get('status', 0)} "
                  f"peer={rec.get('peer', '')} "
                  f"trace_id={rec.get('trace_id', '')}  "
                  f"(drill in: cli.py trace <tracker> "
                  f"--trace-id {rec.get('trace_id', '')})")
        if not slow:
            print("no slow-request records")
        return 0
    agg = aggregate(args.log)
    if args.json:
        json.dump(agg, sys.stdout, indent=2)
        print()
        return 0
    for op, row in agg.items():
        print(f"{op}: n={row['count']} err={row['errors']} "
              f"bytes={row['bytes']} mean={row['mean_us']}us "
              f"p50={row['p50_us']}us p95={row['p95_us']}us "
              f"p99={row['p99_us']}us")
        shares = " ".join(f"{k}={v:.1%}" for k, v in
                          row["stage_share"].items() if v > 0.0005)
        if shares:
            print(f"  {shares}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
