"""The spans inside the sidecar and the engine (``fastdfs_tpu/dedup/spans.py``).

One ``--platform cpu`` sidecar is traced over its own socket (``trace start
<dir>`` / ``trace stop``) while it serves one request of each fingerprint
opcode and one scrubber batch; the tests read the ``.xplane.pb`` it wrote
(``jax.profiler.ProfileData``: no backend is touched) and its ``stats``.
"""

from __future__ import annotations

import glob
import hashlib
import os
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from fastdfs_tpu.common.protocol import StorageCmd
from fastdfs_tpu.dedup import spans as spans_mod
from fastdfs_tpu.sidecar import REINDEX_SESSION_BIT, read_stats, rpc
from harness import REPO, Sidecar

FP, FP_CUTS = StorageCmd.DEDUP_FINGERPRINT, StorageCmd.DEDUP_FINGERPRINT_CUTS
REINDEX_1002 = 1002 | REINDEX_SESSION_BIT   # a session the daemon re-indexes

# The table of OPERATIONS.md, "Tracing".
ENGINE_STAGES = {"fdfs.engine.slot_wait", "fdfs.engine.pack",
                 "fdfs.engine.dispatch", "fdfs.engine.fetch",
                 "fdfs.engine.scatter"}
CHILDREN = ENGINE_STAGES | {"fdfs.sidecar.parse", "fdfs.engine.fingerprint",
                            "fdfs.sidecar.lock_wait", "fdfs.sidecar.reply"}
TABLE = CHILDREN | {"fdfs.sidecar.recv", "fdfs.sidecar.request",
                    "fdfs.sidecar.send", "fdfs.sidecar.verify"}
MARKER = "fdfs.sidecar.request_done"


def wait_for(cond, what: str, limit_s: float = 20.0):
    deadline = time.monotonic() + limit_s
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.02)


def folded(sock: str, sends: int) -> dict:
    """``stats`` once the sidecar has folded that many sends (a request's
    spans are folded just after its reply has gone out)."""
    box = {}

    def there():
        box["s"] = read_stats(sock)
        return box["s"]["span_n"].get("fdfs.sidecar.send", 0) >= sends
    wait_for(there, f"{sends} folded requests")
    return box["s"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    base = str(tmp_path_factory.mktemp("spans"))
    sc = Sidecar(os.path.join(base, "sc"), ("--platform", "cpu"))
    try:
        rng = np.random.default_rng(26)
        # 120: the sidecar cuts (XLA's CPU gear pass is slow: keep it small).
        plain = rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes()
        # 125: 600 cuts of 2 KB are three tiles of one bucket, so the third
        # waits for a staging slot.
        cut = rng.integers(0, 256, 600 * 2048, dtype=np.uint8).tobytes()
        ends = [2048 * (i + 1) for i in range(600)]
        bodies = {
            FP: struct.pack(">qq", 1001, 4096) + plain,
            FP_CUTS: struct.pack(">qqq", REINDEX_1002, 0, len(ends))
            + struct.pack(f">{len(ends)}q", *ends) + cut}
        payload = {FP: plain, FP_CUTS: cut}
        chunk = b"scrubbed chunk"
        verify = (struct.pack(">qq", 1, len(chunk))
                  + hashlib.sha1(chunk).digest() + chunk)

        trace_dir = os.path.join(base, "a trace")   # blanks are taken
        before = folded(sc.sock, 0)
        n0 = before["span_n"].get("fdfs.sidecar.send", 0)
        assert rpc(sc.sock, StorageCmd.DEDUP_COMMIT,
                   f"trace start {trace_dir}".encode(), 300.0) == (0, b"")
        busy, _ = rpc(sc.sock, StorageCmd.DEDUP_COMMIT,
                      f"trace start {trace_dir}".encode(), 300.0)
        replies = {cmd: rpc(sc.sock, cmd, body, 600.0)
                   for cmd, body in bodies.items()}
        verified = rpc(sc.sock, StorageCmd.DEDUP_VERIFY, verify, 600.0)
        assert rpc(sc.sock, StorageCmd.DEDUP_COMMIT, b"trace stop",
                   300.0) == (0, b"")
        idle, _ = rpc(sc.sock, StorageCmd.DEDUP_COMMIT, b"trace stop")
        # stats x1, trace start x2, two fingerprints, verify, trace stop x2
        after = folded(sc.sock, n0 + 8)
        untraced = {cmd: rpc(sc.sock, cmd, body, 600.0)
                    for cmd, body in bodies.items()}
        # (folded under the lock a request holds for its reply: no wait)
        untraced_stall_us = (read_stats(sc.sock)["host_stall_us"]
                             - after["host_stall_us"])

        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        assert len(found) == 1, found
        from jax.profiler import ProfileData
        events = []     # (name, thread, start, end, arguments)
        thread = 0
        for plane in ProfileData.from_file(found[0]).planes:
            for line in plane.lines:
                mine = [(ev.name, thread, ev.start_ns,
                         ev.start_ns + ev.duration_ns, dict(ev.stats))
                        for ev in line.events if ev.name.startswith("fdfs.")]
                events += mine
                thread += 1
        yield {"sock": sc.sock, "events": events, "before": before,
               "after": after, "replies": replies, "untraced": untraced,
               "payload": payload, "verified": verified,
               "untraced_stall_us": untraced_stall_us,
               "second_start": busy, "second_stop": idle, "base": base}
    finally:
        sc.stop()


def request_of(traced, cmd):
    roots = [e for e in traced["events"]
             if e[0] == "fdfs.sidecar.request" and e[4].get("cmd") == cmd]
    assert len(roots) == 1
    return roots[0]


@pytest.mark.parametrize("cmd", [FP, FP_CUTS])
def test_every_span_of_the_table_lies_inside_its_request(traced, cmd):
    _, thread, start, end, args = request_of(traced, cmd)
    inside = [e for e in traced["events"]
              if e[1] == thread and start <= e[2] and e[3] <= end
              and e[0] not in ("fdfs.sidecar.request", MARKER)]
    names = {e[0] for e in inside}
    # only the request with three tiles of one bucket waits for a slot
    want = CHILDREN - ({"fdfs.engine.slot_wait"} if cmd == FP else set())
    assert names == want
    # ... and nowhere else: no child of this request escaped its root
    session = args["session"]
    assert session == {FP: 1001, FP_CUTS: REINDEX_1002}[cmd]
    # bytes the daemon already stores, sent for their signature, say so
    assert args["reindex"] == {FP: 0, FP_CUTS: 1}[cmd]
    assert args["base_offset"] == {FP: 4096, FP_CUTS: 0}[cmd]
    assert args["bytes"] == len(traced["payload"][cmd]) + {
        FP: 16, FP_CUTS: 24 + 8 * 600}[cmd]
    # the engine's stages lie inside the engine's span, one per tile at most
    (eng,) = [e for e in inside if e[0] == "fdfs.engine.fingerprint"]
    for e in inside:
        if e[0] in ENGINE_STAGES:
            assert eng[2] <= e[2] and e[3] <= eng[3]
    tiles = sum(e[0] == "fdfs.engine.pack" for e in inside)
    assert sum(e[0] == "fdfs.engine.dispatch" for e in inside) == tiles >= 1
    if cmd == FP_CUTS:      # 600 rows of one bucket: never a span per chunk
        assert tiles == 3
        assert sum(e[0] == "fdfs.engine.slot_wait" for e in inside) == 1
    # only the identifiers and sizes that something reads ride on a span:
    # a dispatch carries its tile's SHA-1 launch (the benchmark's
    # sha1_lane_fill and sha1_serial_steps_per_MB sum these)
    launches = [e[4] for e in inside if e[0] == "fdfs.engine.dispatch"]
    assert all(set(a) == {"rows", "lanes", "blen", "blocks", "width_blocks"}
               for a in launches)
    # blocks: what the launch walked (under 128 rows as far as its longest
    # chunk, in groups of 8), of width_blocks, the blocks of its width
    assert all(0 < a["rows"] <= a["lanes"] and a["lanes"] % 128 == 0
               and 0 < a["blocks"] <= a["width_blocks"]
               and a["width_blocks"] > a["blen"] // 64 for a in launches)
    if cmd == FP_CUTS:      # every one of the 600 chunks on one row
        assert sum(a["rows"] for a in launches) == 600
    # a pack carries the chunks it packed and the bytes it zeroed
    packs = [e[4] for e in inside if e[0] == "fdfs.engine.pack"]
    assert all(set(a) == {"rows", "zeroed"} for a in packs)
    assert [a["rows"] for a in packs] == [a["rows"] for a in launches]
    if cmd == FP_CUTS:      # three tiles of 256 x 2 KiB hold 600 chunks
        assert sum(a["zeroed"] for a in packs) == (3 * 256 - 600) * 2048
    assert all(set(e[4]) <= {"cmd", "bytes"} for e in inside
               if e[0] not in ("fdfs.engine.dispatch", "fdfs.engine.pack"))
    # recv before the root and send after it, on the same thread
    wire = {e[0]: e for e in traced["events"] if e[1] == thread
            and e[0] in ("fdfs.sidecar.recv", "fdfs.sidecar.send")
            and e[4].get("cmd") == cmd}
    assert wire["fdfs.sidecar.recv"][3] <= start
    assert wire["fdfs.sidecar.send"][2] >= end
    assert wire["fdfs.sidecar.recv"][4]["bytes"] == args["bytes"]


@pytest.mark.parametrize("cmd", [FP, FP_CUTS])
def test_one_marker_per_request_carries_its_counts(traced, cmd):
    _, thread, start, end, _ = request_of(traced, cmd)
    markers = [e for e in traced["events"] if e[0] == MARKER
               and e[1] == thread and start <= e[2] <= end]
    assert len(markers) == 1
    got = markers[0][4]
    assert got["bytes"] == len(traced["payload"][cmd])
    assert set(got) == {"bytes", "host_wall_us", "host_cpu_us", "mono_us"}
    # the anchor: this host's CLOCK_MONOTONIC, read beside the marker
    assert 0 < time.monotonic_ns() // 1000 - got["mono_us"] < 3600 * 10 ** 6
    assert traced["replies"][cmd][0] == 0
    assert 0 < got["host_cpu_us"] <= got["host_wall_us"] * 1.05 + 50
    assert len([e for e in traced["events"] if e[0] == MARKER]) == 2


def test_verify_and_other_opcodes_have_a_root_too(traced):
    assert traced["verified"] == (0, b"\x00")
    root = request_of(traced, int(StorageCmd.DEDUP_VERIFY))
    (verify,) = [e for e in traced["events"] if e[0] == "fdfs.sidecar.verify"]
    assert verify[1] == root[1] and root[2] <= verify[2] and verify[3] <= root[3]
    assert "session" not in root[4]
    # the second `trace start` was answered inside the trace it bounced off
    commits = [e for e in traced["events"] if e[0] == "fdfs.sidecar.request"
               and e[4].get("cmd") == int(StorageCmd.DEDUP_COMMIT)]
    assert commits


def test_stats_fold_the_spans_and_keep_the_old_keys(traced):
    before, after = traced["before"], traced["after"]
    assert set(after["span_us"]) == TABLE == set(after["span_n"])
    d = {k: after[k] - before[k] for k in (
        "fingerprint_bytes", "chunks", "requests", "engine_us",
        "lock_wait_us", "verify_host_fallbacks", "host_stall_us")}
    assert d["fingerprint_bytes"] == sum(map(len, traced["payload"].values()))
    assert d["chunks"] == sum(struct.unpack_from(">q", r)[0]
                              for _, r in traced["replies"].values())
    # trace start x2, two fingerprints, verify, trace stop x2, and the
    # stats request that read `after` (a reply counts its own request)
    assert d["requests"] >= 8
    assert d["verify_host_fallbacks"] == 0
    assert d["lock_wait_us"] >= 0
    stages = sum(after["span_us"][n] - before["span_us"].get(n, 0)
                 for n in ENGINE_STAGES)
    whole = (after["span_us"]["fdfs.engine.fingerprint"]
             - before["span_us"].get("fdfs.engine.fingerprint", 0))
    # the old counters are two of the spans, on the spans' clock
    assert 0 < stages <= whole == d["engine_us"]
    assert d["lock_wait_us"] == (
        after["span_us"]["fdfs.sidecar.lock_wait"]
        - before["span_us"].get("fdfs.sidecar.lock_wait", 0))
    assert after["span_n"]["fdfs.engine.fingerprint"] == 2
    assert after["span_n"]["fdfs.sidecar.verify"] == 1
    assert after["span_n"]["fdfs.engine.slot_wait"] == 1
    # the pack's counters: every chunk's row once, its bytes copied, and
    # zeroed what its pack spans say (tails and empty rows: rows x width
    # of the tiles less the bytes copied); no tile is wide enough to be
    # packed by calls that let the interpreter go
    p = {k: after[k] - before[k] for k in (
        "pack_rows", "pack_rows_released", "pack_copied_bytes",
        "pack_zeroed_bytes", "rows_placed")}
    assert p["pack_rows"] == p["rows_placed"] == d["chunks"]
    assert p["pack_copied_bytes"] == d["fingerprint_bytes"]
    assert p["pack_zeroed_bytes"] == sum(
        e[4]["zeroed"] for e in traced["events"]
        if e[0] == "fdfs.engine.pack") > 0
    assert p["pack_rows_released"] == 0
    # wall >= CPU by construction of the helper, up to the clocks' grain
    assert d["host_stall_us"] >= -50 * 16
    assert after["memory_peak_bytes"] >= 0 and after["backend"] == "cpu"


def test_trace_requests_bounce_and_untraced_replies_are_the_same(traced):
    assert traced["second_start"] == 16     # EBUSY: one runs already
    assert traced["second_stop"] == 16      # none runs
    assert rpc(traced["sock"], StorageCmd.DEDUP_COMMIT, b"trace")[0] == 22
    assert rpc(traced["sock"], StorageCmd.DEDUP_COMMIT, b"trace begin x")[0] == 22
    # the same bytes fingerprinted with no trace running: the same reply,
    # and the thread's CPU clock was not read for them
    assert traced["untraced"] == traced["replies"]
    assert traced["untraced_stall_us"] == 0


def test_a_commit_and_the_merge_it_sets_off_are_spans_and_counters(
        tmp_path):
    """A commit's hold of the bookkeeping lock is ``fdfs.sidecar.commit``;
    the exact index counts its batch and digests, and a batch that fills
    the delta folds it into the base inside a ``fdfs.exact.merge``."""
    from fastdfs_tpu.sidecar import DedupSidecar

    sc = DedupSidecar(str(tmp_path / "s.sock"))
    ready = threading.Event()
    server = threading.Thread(target=sc.serve_forever, args=(ready,),
                              daemon=True)
    server.start()
    try:
        assert ready.wait(60)
        rng = np.random.default_rng(29)

        def upload(session: int, fid: str) -> int:
            """One segment of 200 chunks of 1 KB, then the commit: the
            stats once both are folded."""
            data = rng.integers(0, 256, 200 * 1024, dtype=np.uint8).tobytes()
            ends = [1024 * (i + 1) for i in range(200)]
            body = (struct.pack(">qqq", session, 0, 200)
                    + struct.pack(">200q", *ends) + data)
            sends = read_stats(sc.socket_path)["span_n"].get(
                "fdfs.sidecar.send", 0)
            assert rpc(sc.socket_path, FP_CUTS, body, 600.0)[0] == 0
            assert rpc(sc.socket_path, StorageCmd.DEDUP_COMMIT,
                       f"commitchunks {session} {fid}".encode()) == (0, b"")
            return folded(sc.socket_path, sends + 3)

        first = upload(11, "group1/M00/00/00/one.bin")
        assert (first["exact_insert_batches"], first["exact_inserted"],
                first["exact_merges"]) == (1, 200, 0)
        assert first["span_n"]["fdfs.sidecar.commit"] == 1
        assert first["span_us"]["fdfs.sidecar.commit"] >= 0
        assert "fdfs.exact.merge" not in first["span_n"]
        # fill the delta to one row short of the merge, then commit
        exact = sc.engine.exact
        exact.insert_batch(rng.bytes(20 * (65536 - 201)), "filler",
                           np.arange(65536 - 201))
        assert exact._delta_rows == 65535 and exact.merges == 0
        second = upload(12, "group1/M00/00/00/two.bin")
        assert (second["exact_insert_batches"], second["exact_inserted"],
                second["exact_merges"]) == (3, 65735, 1)
        assert second["span_n"]["fdfs.sidecar.commit"] == 2
        assert second["span_n"]["fdfs.exact.merge"] == 1
        assert len(exact._base) == 65735 and exact._delta_rows == 0
    finally:
        sc.stop()
        server.join(30)
    assert not server.is_alive()


def test_helper_costs_a_flag_test_when_no_trace_runs():
    acc = spans_mod.new_acc()
    with spans_mod.span("fdfs.test.a", acc, cmd=1) as s:
        with spans_mod.span("fdfs.test.b", acc, True) as inner:
            sum(range(1000))
    assert s.ann is None                      # no annotation was made
    spans_mod.mark("fdfs.test.done", bytes=1)   # and none here
    assert acc["span_n"] == {"fdfs.test.a": 1, "fdfs.test.b": 1}
    assert acc["span_ns"]["fdfs.test.a"] >= acc["span_ns"]["fdfs.test.b"] > 0
    # the CPU clock is a system call: read only while a trace runs
    assert not inner.cpu
    assert (acc["host_cpu_ns"], acc["host_wall_ns"]) == (0, 0)


def test_cli_sidecar_trace_prints_the_deltas(traced):
    out_dir = os.path.join(traced["base"], "cli_trace")
    body = (struct.pack(">qq", 1003 | REINDEX_SESSION_BIT, 0)
            + traced["payload"][FP][:70_000])
    done = threading.Event()

    def keep_sending():     # so that some request falls between its reads
        while not done.wait(0.3):
            rpc(traced["sock"], FP, body, 600.0)
    sender = threading.Thread(target=keep_sending, daemon=True)
    sender.start()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "fastdfs_tpu.cli", "sidecar-trace",
             traced["sock"], "--seconds", "3", "--out", out_dir],
            cwd=REPO, capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
    finally:
        done.set()
        sender.join(60)
    assert not sender.is_alive()
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))
    rows = {ln.split()[0]: ln.split() for ln in proc.stdout.splitlines()
            if ln.startswith("fdfs.")}
    assert set(rows) == TABLE
    assert int(rows["fdfs.engine.fingerprint"][1]) >= 1
    assert float(rows["fdfs.engine.fingerprint"][2]) > 0
    assert "MB fingerprinted" in proc.stdout
    assert "device memory peak" in proc.stdout
    # the near-dup index's gauges and counters (nothing asked it here)
    (near,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("near-dup index:")]
    assert "near_rows" in near and "near_resident_bytes" in near
    assert "near_queries 0 in near_scans 0" in near
    # the exact index's counters (nothing committed here)
    (exact,) = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("exact index:")]
    assert "exact_insert_batches 0, exact_inserted 0" in exact
    assert "exact_merges 0" in exact
    # the erasure coding's counters (no stripe encoded here)
    (ec,) = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("erasure coding:")]
    assert "ec_encode_requests 0" in ec and "ec_encode_bytes 0.0 MB" in ec
    # ... all of them a re-index here, told apart from uploads
    (reidx,) = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("of them re-index:")]
    assert int(reidx.split()[6]) >= 1 and float(reidx.split()[3]) > 0
    # the receive counters beside the spans: a 70 KB body is one call
    (recv,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("fingerprint bodies:")]
    bodies, calls = int(recv.split()[2].rstrip(",")), int(recv.split()[7])
    assert bodies == int(rows["fdfs.sidecar.parse"][1]) >= 1
    assert calls == bodies and "(1.00 a body)" in recv
    # the host path (--platform cpu) places no tile on a device
    assert "tiles_by_rows: none" in proc.stdout
    # ... and counts its launches' arithmetic all the same
    (sha1,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("sha1 launches:")]
    walked, width = int(sha1.split()[3]), int(sha1.split()[6])
    assert 0 < walked <= width and "of the widths walked)" in sha1
    # ... and the pack of those rows: no tile wide enough to let the
    # interpreter go, every row's chunk copied, tails zeroed
    launch, pack = sha1.split("; ")
    placed, pack = int(launch.split()[-5]), pack.split()
    assert pack[:4] == ["pack_rows", str(placed), "(pack_rows_released",
                        "0),"]
    assert pack[4] == "pack_copied_bytes" and float(pack[5]) > 0
    assert pack[7] == "pack_zeroed_bytes" and float(pack[8]) > 0
