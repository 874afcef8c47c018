"""The storage daemon's stages as real intervals, on the device trace's clock.

One sidecar on the CPU, traced over its own socket, behind one storage
daemon with the access log on and 1 MB segments:

* a plain upload over two segments shows two ``storage.fingerprint``
  intervals in its stage line, each holding its ``storage.cdc``,
  ``storage.fp_lock`` and ``storage.fp_rpc``, none overlapping the other
  segment's, and every access-log column equal to the sum of its intervals;
* a negotiated commit the same for verify / present / reindex;
* a request that carried ``TRACE_CTX`` gives the same intervals through
  ``TRACE_DUMP``, and ``render_timeline`` draws a request from the log;
* the sidecar's markers carry ``mono_us``, and ``benchmark/daemon_spans.py``
  finds every ``fdfs.sidecar.request`` of the trace inside the daemon's
  ``storage.fp_rpc`` of the same session and base offset;
* every reader of the access log that exists returns the same rows from a
  log with and without stage lines;
* ``fdfs_codec stage-line`` (the native formatter) decodes field for field,
  and an interval costs what OPERATIONS.md says it does.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from fastdfs_tpu import trace as T
from fastdfs_tpu.client import StorageClient
from fastdfs_tpu.common.protocol import StorageCmd
from fastdfs_tpu.sidecar import REINDEX_SESSION_BIT, rpc
from harness import BUILD, REPO, Sidecar, ensure_native_built, start_storage

sys.path.insert(0, os.path.join(REPO, "benchmark"))
sys.path.insert(0, os.path.join(REPO, "tools"))
import access_log_stages  # noqa: E402
import daemon_spans  # noqa: E402  — benchmark/daemon_spans.py
import host_spans  # noqa: E402
import run as bench_run  # noqa: E402  — benchmark/run.py
from layer_metrics import _negotiated  # noqa: E402

K, M = 1 << 10, 1 << 20
NARROW = (4 * K, 13, 64 * K)
# name -> position of its column in a row of the access log
COLUMN = {"storage.recv": 6, "storage.fingerprint": 8, "storage.fp_lock": 9,
          "storage.cs_write": 10, "storage.binlog": 11, "storage.cdc": 13,
          "dio.queue_wait": 14, "storage.tmp_readback": 15,
          "storage.negotiate": 16, "storage.commit.present": 17,
          "storage.commit.verify": 18, "storage.commit.recipe": 19,
          "storage.reindex": 20}
TRACE_ID = 0x5EED0000CAFE0001


def _seeded(n: int, seed: int) -> bytes:
    return np.random.default_rng(seed).bytes(n)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(rows and stage lines of the access log by command, TRACE_DUMP spans
    of the traced upload, the sidecar's trace as host_spans loads it)."""
    base = str(tmp_path_factory.mktemp("stages"))
    sidecar = Sidecar(os.path.join(base, "sc"), (
        "--platform", "cpu", "--cdc-widths", "%d:%d:%d" % NARROW))
    st = start_storage(
        os.path.join(base, "st"), dedup_mode="sidecar",
        dedup_sidecar=sidecar.sock,
        extra=("dedup_cdc_widths = %d:%d:%d\n" % NARROW
               + "dedup_segment_bytes = 1M\nuse_access_log = 1"))
    trace_dir = os.path.join(base, "trace")
    gen0 = _seeded(M + M // 2, 71)                  # two segments
    gen1 = gen0[:300 * K] + _seeded(5000, 72) + gen0[308 * K:]
    try:
        assert rpc(sidecar.sock, StorageCmd.DEDUP_COMMIT,
                   f"trace start {trace_dir}".encode(), 300.0) == (0, b"")
        with StorageClient(st.ip, st.port, timeout=300.0) as sc:
            sc.conn.trace_ctx = T.TraceContext(TRACE_ID, 0x21)
            sc.upload_buffer(gen0, ext="bin")
            sc.conn.trace_ctx = None
            stats: dict = {}
            sc.upload_buffer_dedup(gen1, ext="bin", stats=stats)
            assert stats["fallback"] == ""
            dump = [s for s in T.decode_dump(sc.trace_dump())
                    if s.trace_id == TRACE_ID]
        assert rpc(sidecar.sock, StorageCmd.DEDUP_COMMIT, b"trace stop",
                   300.0) == (0, b"")
    finally:
        st.stop()           # flushes the access log
        sidecar.stop()
    log_path = os.path.join(base, "st", "logs", "access.log")
    by_cmd: dict[int, list] = {}
    with open(log_path) as fh:
        lines = fh.read().splitlines()
    for i, line in enumerate(lines):
        if line.startswith('{"event":"stages"'):
            # the intervals follow their request's row
            row = lines[i - 1].split()
            rec = json.loads(line)
            assert int(row[2]) == rec["cmd"] and " " not in line
            by_cmd.setdefault(rec["cmd"], []).append((row, rec))
    (xplane,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    return {"by_cmd": by_cmd, "dump": dump, "log": log_path,
            "trace": host_spans.load(xplane)}


def _spans(rec: dict, name: str) -> list[tuple]:
    return [(i, sp) for i, sp in enumerate(rec["spans"]) if sp[0] == name]


def _assert_well_formed(row: list[str], rec: dict) -> None:
    """Children inside parents, the request's thread never in two sibling
    intervals at once, and each column the sum of its stage's intervals."""
    spans = rec["spans"]
    assert rec["truncated"] == 0 and rec["dur_us"] == int(row[5])
    for i, (name, off, dur, parent, *_) in enumerate(spans):
        assert off >= 0 and dur >= 0 and off + dur <= rec["dur_us"], name
        if parent >= 0:
            assert parent < i
            _, p_off, p_dur, *_ = spans[parent]
            assert p_off <= off and off + dur <= p_off + p_dur, name
    for parent in {sp[3] for sp in spans}:
        level = [sp for sp in spans if sp[3] == parent]
        for a, b in zip(level, level[1:]):
            assert a[1] + a[2] <= b[1], (a, b)
    for name, col in COLUMN.items():
        assert int(row[col]) == sum(sp[2] for sp in spans if sp[0] == name), \
            name


def test_plain_upload_over_two_segments_has_real_intervals(traced):
    (row, rec), = [x for x in traced["by_cmd"][11] if int(x[0][12]) > M]
    _assert_well_formed(row, rec)
    fps = _spans(rec, "storage.fingerprint")
    assert len(fps) == 2 and len(_spans(rec, "storage.tmp_readback")) == 2
    sessions = set()
    for (i, fp), base in zip(fps, (0, M)):
        kids = {sp[0]: sp for sp in rec["spans"] if sp[3] == i}
        assert set(kids) == {"storage.cdc", "storage.fp_lock",
                             "storage.fp_rpc"}
        cdc, lock, call = (kids[n] for n in ("storage.cdc", "storage.fp_lock",
                                             "storage.fp_rpc"))
        assert cdc[1] + cdc[2] <= lock[1] and lock[1] + lock[2] <= call[1]
        assert call[4]["base_offset"] == base
        sessions.add(call[4]["session"])
    assert len(sessions) == 1 and not sessions.pop() & REINDEX_SESSION_BIT
    # segment by segment: read back, fingerprint, store; then recipe, binlog
    top = [sp[0] for sp in rec["spans"] if sp[3] == -1]
    assert top == ["storage.recv", "dio.queue_wait"] + [
        "storage.tmp_readback", "storage.fingerprint",
        "storage.cs_write"] * 2 + ["storage.cs_write", "storage.binlog"]
    assert int(row[8]) > 0 and int(row[13]) > 0 and int(row[19]) == 0


def test_negotiated_commit_has_real_intervals(traced):
    (row, rec), = traced["by_cmd"][133]
    _assert_well_formed(row, rec)
    top = [sp[0] for sp in rec["spans"] if sp[3] == -1]
    assert top == ["storage.recv", "dio.queue_wait"] + [
        "storage.cs_write", "storage.reindex"] * 2 + [
        "storage.cs_write", "storage.reindex", "storage.binlog"]
    writes = _spans(rec, "storage.cs_write")
    for i, _ in writes[:2]:
        assert [sp[0] for sp in rec["spans"] if sp[3] == i] == [
            "storage.commit.verify", "storage.commit.present"]
    assert [sp[0] for sp in rec["spans"] if sp[3] == writes[2][0]] == [
        "storage.commit.recipe"]
    reindex = _spans(rec, "storage.reindex")
    for (i, _), base in zip(reindex[:2], (0, M)):
        kids = {sp[0]: sp for sp in rec["spans"] if sp[3] == i}
        assert set(kids) == {"storage.cdc", "storage.fp_lock",
                             "storage.fp_rpc"}
        args = kids["storage.fp_rpc"][4]
        assert args["base_offset"] == base
        assert args["session"] & REINDEX_SESSION_BIT
    # the session's commit holds no fingerprint RPC
    assert not [sp for sp in rec["spans"] if sp[3] == reindex[2][0]]
    reads = [sp[4] for _, sp in _spans(rec, "storage.commit.present")]
    assert all(r["read_chunks"] >= r["read_batches"] > 0 for r in reads)
    assert all(int(row[c]) > 0 for c in (10, 17, 18, 19, 20))
    # and the negotiation before it
    (nrow, nrec), = traced["by_cmd"][132]
    _assert_well_formed(nrow, nrec)
    assert [sp[0] for sp in nrec["spans"]] == ["dio.queue_wait",
                                               "storage.negotiate"]


def test_trace_dump_gives_the_logged_intervals(traced):
    (row, rec), = [x for x in traced["by_cmd"][11] if int(x[0][12]) > M]
    dump = sorted(traced["dump"], key=lambda s: (s.start_us, s.span_id))
    (root,) = [s for s in dump if s.name == "storage.upload_file"]
    assert root.parent_id == 0x21 and root.dur_us == rec["dur_us"]
    by_id = {s.span_id: s for s in dump}
    ringed = [s for s in dump if s is not root]
    # the ring leaves out an interval of 0 us; everything else is there,
    # at its offset, for its length, under its parent's name
    logged = [sp for sp in rec["spans"] if sp[2] > 0]
    assert len(ringed) == len(logged)

    def parent_name(sp):
        return rec["spans"][sp[3]][0] if sp[3] >= 0 else root.name
    want = sorted((sp[1], sp[2], sp[0], parent_name(sp)) for sp in logged)
    got = sorted((s.start_us - root.start_us, s.dur_us, s.name,
                  by_id[s.parent_id].name) for s in ringed)
    assert got == want


def test_render_timeline_draws_a_logged_request(traced):
    spans = T.logged_requests(traced["log"],
                              cmd_names=access_log_stages.CMD_NAMES)
    by_trace = T.stitch(spans)
    commit = [tr for tr in by_trace.values()
              if tr[0].name == "storage.upload_chunks"]
    assert len(commit) == 1
    names = [s.name for s in commit[0]]
    # tree order: each segment's cs_write, then verify and present under it
    at = names.index("storage.cs_write")
    assert names[at:at + 3] == ["storage.cs_write", "storage.commit.verify",
                                "storage.commit.present"]
    text = T.render_timeline(spans, commit[0][0].trace_id)
    assert "storage.fp_rpc" in text and "storage.commit.present" in text
    # the operator's way in: the slowest logged request, by the stage tool
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "access_log_stages.py"),
         traced["log"], "--timeline", "1"], capture_output=True, text=True,
        check=True).stdout
    assert "storage.upload " in out and out.count("storage.fingerprint") == 2


def test_sidecar_requests_lie_inside_the_daemons_fp_rpc(traced):
    trace = traced["trace"]
    markers = [ev for ev in host_spans.host_events(trace)
               if ev[0] == host_spans.MARKER]
    assert len(markers) == 4 and all(ev[4]["mono_us"] > 0 for ev in markers)
    lines = daemon_spans.stage_lines(traced["log"])
    got = daemon_spans.summarize(trace, lines)
    assert got["anchors"] == 4 and got["uploads"] == 3
    # two segments of the upload, two of the commit's re-index
    assert (got["rpc_spans_matched"], got["rpc_spans"]) == (4, 4)
    assert got["tolerance_ns"] <= daemon_spans.MAX_TOLERANCE_NS
    assert got["idle_s"] is None        # a CPU trace has no device plane
    # the same spans against a clock that is off by a second
    requests = daemon_spans.on_trace_clock(lines, got["offset_ns"] + 10 ** 9)
    assert daemon_spans.clock_match(host_spans.host_events(trace), requests,
                                    got["tolerance_ns"]) == (0, 4)


# -- the readers of the log that exist take no notice of the stage lines ----------

LOG = """\
1700000000 127.0.0.1 11 0 44 900 100 700 300 5 200 40 70000 50 3 60 0 0 0 0 0
{"event":"stages","cmd":11,"status":0,"t0_mono_us":1,"t0_wall_us":2,"dur_us":900,"truncated":0,"spans":[["storage.recv",0,100,-1]]}
1700000001 127.0.0.1 14 0 70000 120 0 80 0 0 0 0 60 0 2 0 0 0 0 0 0
{"event":"stages","cmd":14,"status":0,"t0_mono_us":3,"t0_wall_us":4,"dur_us":120,"truncated":0,"spans":[["dio.queue_wait",10,2,-1]]}
1700000002 127.0.0.1 132 0 90 400 0 300 0 0 0 0 5000 0 4 0 250 0 0 0 0
{"event":"stages","cmd":132,"status":0,"t0_mono_us":5,"t0_wall_us":6,"dur_us":400,"truncated":0,"spans":[["dio.queue_wait",50,4,-1],["storage.negotiate",54,250,-1]]}
1700000003 127.0.0.1 133 0 44 5000 600 4000 0 7 1500 30 9000 90 5 0 0 800 500 200 1800
{"event":"stages","cmd":133,"status":0,"t0_mono_us":7,"t0_wall_us":8,"dur_us":5000,"truncated":0,"spans":[["storage.recv",0,600,-1]]}
{"event":"slow_request","role":"storage","op":"storage.upload_chunks","trace_id":"00000000000000aa","span_id":"80000001","start_us":8,"dur_us":5000,"status":0,"peer":"127.0.0.1","bytes":44,"thread":"dio-0"}
1700000004 127.0.0.1 11 0 44 1900 200 1500 800 9 400 45 140000 120 6 130 0 0 0 0 0
{"event":"stages","cmd":11,"status":0,"t0_mono_us":9,"t0_wall_us":10,"dur_us":1900,"truncated":1,"spans":[]}
"""


def _cell(tmp_path, text: str) -> dict:
    logs = tmp_path / "st" / "logs"
    logs.mkdir(parents=True, exist_ok=True)
    (logs / "access.log").write_text(text)
    return {"sidecar": types.SimpleNamespace(
        bench_dir=str(tmp_path / "sc" / "bench")), "preloaded_files": 1}


READERS = {
    "run.upload_rows": lambda cell, path: bench_run.upload_rows(path),
    "host_spans.late_columns": lambda cell, path: host_spans.late_columns(cell),
    "_negotiated.rows": lambda cell, path: _negotiated.rows(cell),
    "access_log_stages.aggregate":
        lambda cell, path: access_log_stages.aggregate(path),
    "access_log_stages.slow_requests":
        lambda cell, path: access_log_stages.slow_requests(path),
}


@pytest.mark.parametrize("reader", sorted(READERS))
def test_readers_of_the_log_skip_the_stage_lines(tmp_path, reader):
    without = "".join(ln + "\n" for ln in LOG.splitlines()
                      if '"event":"stages"' not in ln)
    assert without != LOG
    got = []
    for name, text in (("with", LOG), ("without", without)):
        cell = _cell(tmp_path / name, text)
        got.append(READERS[reader](
            cell, str(tmp_path / name / "st" / "logs" / "access.log")))
    assert got[0] == got[1] and got[0]
    # and the stage lines are all there for the reader that wants them
    assert len(daemon_spans.stage_lines(
        str(tmp_path / "with" / "st" / "logs" / "access.log"))) == 5


# -- the native formatter and the recorder's cost ---------------------------------

def test_native_stage_line_golden():
    codec = os.path.join(BUILD, "fdfs_codec")
    ensure_native_built((codec,))
    out = subprocess.run([codec, "stage-line"], capture_output=True,
                         check=True, text=True).stdout
    assert out.count("\n") == 1 and " " not in out
    spans = T.decode_stage_line(out, seq=9, cmd_names={11: "upload_file"})
    # Fixture from native/tools/codec_cli.cc, field for field.
    root = spans[0]
    assert (root.name, root.trace_id, root.span_id, root.parent_id) == (
        "storage.upload_file", 9, 1, 0)
    assert (root.start_us, root.dur_us) == (1700000000000000, 6900)
    assert [s.name for s in spans[1:]] == [
        "storage.recv", "dio.queue_wait"] + [
        "storage.tmp_readback", "storage.fingerprint", "storage.cdc",
        "storage.fp_lock", "storage.fp_rpc", "storage.cs_write"] * 2 + [
        "storage.binlog"]
    by_id = {s.span_id: s for s in spans}
    for s in spans[1:]:
        want = ("storage.fingerprint" if s.name in (
            "storage.cdc", "storage.fp_lock", "storage.fp_rpc")
            else "storage.upload_file")
        assert by_id[s.parent_id].name == want
        assert by_id[s.parent_id].start_us <= s.start_us
        assert s.end_us <= by_id[s.parent_id].end_us
    rpcs = [s for s in spans if s.name == "storage.fp_rpc"]
    assert [(s.start_us - root.start_us, s.dur_us) for s in rpcs] == [
        (1725, 1480), (4725, 1480)]
    rec = json.loads(out)
    assert [sp[4] for sp in rec["spans"] if len(sp) > 4] == [
        {"session": (1234 << 32) | 7, "base_offset": 0},
        {"session": (1234 << 32) | 7, "base_offset": 64 * M}]
    assert T.decode_stage_line("1700000000 127.0.0.1 11 0 1 2 3") == []
    with pytest.raises(ValueError):
        T.decode_stage_line('{"event":"stages","cmd":11}')


def test_an_interval_costs_two_clock_reads():
    exe = os.path.join(BUILD, "common_test")
    ensure_native_built((exe,))
    out = subprocess.run([exe, "--stage-cost"], capture_output=True,
                         check=True, text=True).stdout
    got = json.loads(out.strip().splitlines()[-1])
    assert got["recorded"] == 10 and got["intervals"] == 2_000_000
    # 67 ns here, 60-80 on the chip's host (OPERATIONS.md, "Tracing");
    # the limit only catches a lock, an allocation or a system call
    assert got["ns_per_interval"] < 2000
    assert got["ns_per_null_guard"] < got["ns_per_interval"]
