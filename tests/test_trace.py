"""Distributed request tracing: trace-context wire contract, span
stitching, the fdfs_codec cross-language goldens, and live-cluster
integration (ISSUE 2 acceptance: one traced upload through a
1-tracker/2-storage cluster yields a stitched timeline with client,
tracker, storage, and replication-sync spans sharing one trace_id,
while an untraced client works unchanged).
"""

import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from fastdfs_tpu import trace as T
from fastdfs_tpu.common import protocol as P
from tests.harness import (BUILD, REPO, STORAGED, TRACKERD, start_storage,
                           start_tracker, upload_retry)

_HAVE_TOOLCHAIN = ((shutil.which("cmake") is not None
                    and shutil.which("ninja") is not None)
                   or shutil.which("g++") is not None)
_HAVE_BINARIES = os.path.exists(STORAGED) and os.path.exists(TRACKERD)
needs_native = pytest.mark.skipif(
    not (_HAVE_TOOLCHAIN or _HAVE_BINARIES),
    reason="no native toolchain and no prebuilt daemons")

HB = "heart_beat_interval = 1\nstat_report_interval = 1"


# ---------------------------------------------------------------------------
# wire contract (pure Python)
# ---------------------------------------------------------------------------

def test_trace_ctx_pack_roundtrip():
    body = P.pack_trace_ctx(0x0102030405060708, 0xAABBCCDD, 3)
    assert len(body) == P.TRACE_CTX_LEN == 16
    # Big-endian layout golden: 8B trace_id + 4B span + 4B flags.
    assert body.hex() == "0102030405060708aabbccdd00000003"
    assert P.unpack_trace_ctx(body) == (0x0102030405060708, 0xAABBCCDD, 3)
    with pytest.raises(ValueError):
        P.unpack_trace_ctx(b"short")


def test_trace_ctx_frame_shape():
    ctx = T.TraceContext(trace_id=7, span_id=9, flags=1)
    frame = ctx.frame()
    assert len(frame) == P.HEADER_SIZE + P.TRACE_CTX_LEN
    hdr = P.unpack_header(frame[:P.HEADER_SIZE])
    # Same opcode value on both ports — one frame serves either daemon.
    assert hdr.cmd == P.StorageCmd.TRACE_CTX == P.TrackerCmd.TRACE_CTX
    assert hdr.pkg_len == P.TRACE_CTX_LEN
    assert T.TraceContext.unpack(frame[P.HEADER_SIZE:]) == ctx


def test_untraced_request_bytes_unchanged():
    # Wire-compat core: with no trace installed, a request is
    # byte-identical to the pre-trace protocol (no prefix frame).
    import socket
    from fastdfs_tpu.client.conn import Connection

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    conn = Connection("127.0.0.1", srv.getsockname()[1], timeout=5)
    peer, _ = srv.accept()
    try:
        conn.send_request(P.StorageCmd.ACTIVE_TEST, b"")
        plain = peer.recv(4096)
        assert plain == P.pack_header(0, P.StorageCmd.ACTIVE_TEST)
        conn.trace_ctx = T.TraceContext(1, 2)
        conn.send_request(P.StorageCmd.ACTIVE_TEST, b"")
        traced = peer.recv(4096)
        assert traced == conn.trace_ctx.frame() + plain
    finally:
        conn.close()
        peer.close()
        srv.close()


# ---------------------------------------------------------------------------
# stitching + rendering (pure Python)
# ---------------------------------------------------------------------------

def _span(tid, sid, parent, name, start, dur, node="n", **kw):
    return T.Span(trace_id=tid, span_id=sid, parent_id=parent, name=name,
                  start_us=start, dur_us=dur, node=node, **kw)


def test_stitch_groups_and_orders():
    spans = [
        _span(1, 10, 0, "client.upload", 100, 50, "client"),
        _span(1, 30, 20, "storage.recv", 120, 5, "storage a"),
        _span(1, 20, 10, "storage.upload_file", 110, 30, "storage a"),
        _span(2, 40, 0, "recovery.file", 500, 9, "storage b"),
    ]
    stitched = T.stitch(spans)
    assert set(stitched) == {1, 2}
    names = [s.name for s in stitched[1]]
    # Parent-before-child tree order, roots by start time.
    assert names == ["client.upload", "storage.upload_file", "storage.recv"]


def test_stitch_orphans_and_cycles_never_hang():
    # Orphan: parent span never collected (overwritten in a ring).
    spans = [_span(1, 2, 999, "storage.binlog", 10, 1)]
    assert [s.name for s in T.stitch(spans)[1]] == ["storage.binlog"]
    # Cycle (colliding span ids): must terminate and keep every span.
    spans = [
        _span(3, 5, 6, "a", 0, 1),
        _span(3, 6, 5, "b", 1, 1),
    ]
    out = T.stitch(spans)[3]
    assert {s.name for s in out} == {"a", "b"}


def test_render_timeline_mentions_nodes_and_flags():
    spans = [
        _span(9, 1, 0, "client.upload", 0, 1000, "client"),
        _span(9, 2, 1, "storage.upload_file", 100, 800, "storage x:1",
              flags=T.TRACE_FLAG_SLOW, status=5),
    ]
    text = T.render_timeline(spans)
    assert "trace 0000000000000009" in text
    assert "client.upload" in text and "storage.upload_file" in text
    assert "SLOW" in text and "status=5" in text
    data = json.loads(T.spans_to_json(spans))
    assert data[0]["trace_id"] == "0000000000000009"


def test_decode_dump_rejects_malformed():
    with pytest.raises(ValueError):
        T.decode_dump({"role": "storage"})           # no spans list
    with pytest.raises(ValueError):
        T.decode_dump({"spans": [{"trace_id": "xx"}]})  # bad fields


def test_tracer_spans_nest_and_wire_ctx():
    tr = T.Tracer()
    assert tr.wire_ctx() is None
    with tr.span("client.upload") as root_ctx:
        assert tr.wire_ctx().span_id == root_ctx.span_id
        with tr.span("client.inner") as inner:
            assert tr.wire_ctx().span_id == inner.span_id
    assert tr.wire_ctx() is None
    by_name = {s.name: s for s in tr.spans}
    assert by_name["client.inner"].parent_id == root_ctx.span_id
    assert by_name["client.upload"].parent_id == 0
    assert all(s.trace_id == tr.trace_id for s in tr.spans)


# ---------------------------------------------------------------------------
# cross-language goldens (fdfs_codec)
# ---------------------------------------------------------------------------

def _ensure_codec() -> str:
    codec = os.path.join(BUILD, "fdfs_codec")
    from tests.harness import ensure_native_built
    ensure_native_built((codec,))
    return codec


@needs_native
def test_native_trace_json_golden():
    codec = _ensure_codec()
    out = subprocess.run([codec, "trace-json"], capture_output=True,
                         check=True)
    spans = T.decode_dump(json.loads(out.stdout))
    # Fixture from native/tools/codec_cli.cc, field for field.
    assert [s.name for s in spans] == [
        "tracker.query_store", "storage.upload_file", "storage.fingerprint"]
    root = spans[1]
    assert root.trace_id == 0x000F00DFACE12345
    assert root.span_id == 0x80000001 and root.parent_id == 0x10
    assert root.start_us == 1700000000000000 and root.dur_us == 1500
    child = spans[2]
    assert child.parent_id == root.span_id
    slow = spans[0]
    assert slow.flags & T.TRACE_FLAG_SLOW and slow.status == 5
    # And the stitcher nests the fixture correctly.
    stitched = T.stitch(spans)
    assert [s.name for s in stitched[root.trace_id]] == [
        "storage.upload_file", "storage.fingerprint"]


@needs_native
def test_native_trace_ctx_wire_golden():
    codec = _ensure_codec()
    body = P.pack_trace_ctx(0x0102030405060708, 0xAABBCCDD, 3)
    out = subprocess.run([codec, "trace-ctx", body.hex()],
                         capture_output=True, check=True)
    assert out.stdout.decode().strip() == (
        "trace_id=0102030405060708 parent=aabbccdd flags=3 roundtrip=1")


# ---------------------------------------------------------------------------
# live cluster integration
# ---------------------------------------------------------------------------

def _wait_active(tracker_port: int, want: int, timeout: float = 20.0):
    from fastdfs_tpu.client import TrackerClient
    deadline = time.time() + timeout
    with TrackerClient("127.0.0.1", tracker_port) as t:
        while time.time() < deadline:
            groups = t.list_groups()
            if groups and groups[0]["active"] >= want:
                return
            time.sleep(0.2)
    raise RuntimeError("storages never went ACTIVE")


@needs_native
def test_traced_upload_stitches_across_cluster(tmp_path):
    """ISSUE 2 acceptance: traced upload through 1 tracker + 2 storages
    produces client, tracker, storage, and replication-sync spans under
    one trace_id, while an untraced client works unchanged."""
    from fastdfs_tpu.client import FdfsClient

    tracker = start_tracker(os.path.join(str(tmp_path), "tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    s1 = start_storage(os.path.join(str(tmp_path), "s1"), trackers=[taddr],
                       extra=HB, ip="127.0.0.2")
    s2 = start_storage(os.path.join(str(tmp_path), "s2"), trackers=[taddr],
                       extra=HB, ip="127.0.0.3")
    cli = FdfsClient([taddr])
    try:
        _wait_active(tracker.port, 2)
        # Untraced traffic against trace-aware daemons: byte-identical
        # wire, everything works (backward compat).
        data = os.urandom(20000)
        fid = upload_retry(cli, data, ext="bin")
        assert cli.download_to_buffer(fid) == data

        fid2, tracer = T.traced_upload(cli, os.urandom(20000), ext="bin")
        assert fid2

        # The sync hop records after the replication ships; poll the
        # cluster dumps rather than sleeping blind.
        deadline = time.time() + 20
        names, mine = set(), []
        while time.time() < deadline:
            spans, errors = T.collect_cluster_spans(cli)
            assert not errors, errors
            mine = [s for s in spans if s.trace_id == tracer.trace_id]
            names = {s.name for s in mine}
            if "sync.ship" in names and "storage.sync_create_file" in names:
                break
            time.sleep(0.3)
        mine.extend(tracer.spans)
        names = {s.name for s in mine}
        assert "client.upload" in names
        assert "tracker.query_store" in names
        assert "storage.upload_file" in names
        assert "sync.ship" in names
        assert "storage.sync_create_file" in names, names
        # Spans from BOTH storage daemons (source + replica).
        storage_nodes = {s.node for s in mine
                         if s.name.startswith(("storage.", "sync."))}
        assert len(storage_nodes) == 2, storage_nodes
        # One trace id everywhere, and the timeline renders it nested.
        assert {s.trace_id for s in mine} == {tracer.trace_id}
        text = T.render_timeline(mine, tracer.trace_id)
        assert "nodes=4" in text, text
        assert cli.download_to_buffer(fid2)  # traced file readable too
    finally:
        cli.close()
        s1.stop()
        s2.stop()
        tracker.stop()


@needs_native
def test_slow_request_force_retained_and_logged(tmp_path):
    """With slow_request_threshold_ms=1 every request trips the slow
    gate: an UNTRACED upload must still land in the span ring (flags
    carry SLOW) and emit one structured JSON line that
    tools/access_log_stages.py ingests."""
    from fastdfs_tpu.client import FdfsClient, StorageClient

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import access_log_stages

    tracker = start_tracker(os.path.join(str(tmp_path), "tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    base = os.path.join(str(tmp_path), "st")
    storage = start_storage(
        base, trackers=[taddr],
        extra=HB + "\nslow_request_threshold_ms = 1\nuse_access_log = true")
    cli = FdfsClient([taddr])
    try:
        _wait_active(tracker.port, 1)
        # 8 MB through loopback: comfortably over the 1 ms threshold
        # (the smallest the ms-granular config key can express).
        fid = upload_retry(cli, os.urandom(8 << 20), ext="bin")
        assert fid
        with StorageClient("127.0.0.1", storage.port) as sc:
            dump = sc.trace_dump()
            spans = T.decode_dump(dump)
            uploads = [s for s in spans if s.name == "storage.upload_file"]
            assert uploads, [s.name for s in spans]
            assert all(s.flags & T.TRACE_FLAG_SLOW for s in uploads)
            # The registry surfaces the slow gate + ring pressure.
            reg = sc.stat()
            assert reg["gauges"]["trace.slow_requests"] >= 1
            assert reg["gauges"]["trace.spans_recorded"] >= len(uploads)
        # The structured line reaches the access log and the daemon log,
        # and the stage tool both skips it (plain parse) and ingests it
        # (--slow parse).
        log_path = os.path.join(base, "logs", "access.log")
        deadline = time.time() + 15
        slow = []
        while time.time() < deadline:
            if os.path.exists(log_path):
                slow = access_log_stages.slow_requests(log_path)
                if slow:
                    break
            time.sleep(0.3)
        assert slow, "no slow-request JSON line ingested"
        assert slow[0]["event"] == "slow_request"
        assert slow[0]["role"] == "storage"
        assert re.fullmatch(r"[0-9a-f]{16}", slow[0]["trace_id"])
        assert slow[0]["dur_us"] >= 1000
        # Plain column aggregation still works on the mixed-format log.
        agg = access_log_stages.aggregate(log_path)
        assert any(row["count"] >= 1 for row in agg.values())
    finally:
        cli.close()
        storage.stop()
        tracker.stop()


@needs_native
def test_chunked_upload_shows_readback_and_cdc_inside_fingerprint(tmp_path):
    """A chunked upload's stages on the fdfs_trace timeline and in the
    access log: the tmp file is read back before the fingerprint stage,
    and the native chunker runs inside it (``storage.cdc`` is a child of
    ``storage.fingerprint``, ``cdc_us`` a share of ``fp_us``)."""
    from fastdfs_tpu.client import StorageClient

    base = os.path.join(str(tmp_path), "st")
    storage = start_storage(
        base, dedup_mode="cpu",
        extra="slow_request_threshold_ms = 1\nuse_access_log = true")
    try:
        with StorageClient("127.0.0.1", storage.port) as sc:
            assert sc.upload_buffer(os.urandom(8 << 20))
            spans = T.decode_dump(sc.trace_dump())
        by_name = {s.name: s for s in spans}
        root = by_name["storage.upload_file"]
        fp, cdc = by_name["storage.fingerprint"], by_name["storage.cdc"]
        readback = by_name["storage.tmp_readback"]
        assert cdc.parent_id == fp.span_id and fp.parent_id == root.span_id
        assert readback.parent_id == root.span_id
        # intervals as they happened: the chunker starts inside the
        # fingerprint call, which starts once the segment is read back
        assert fp.start_us <= cdc.start_us and 0 < cdc.dur_us <= fp.dur_us
        assert cdc.start_us + cdc.dur_us <= fp.start_us + fp.dur_us
        assert readback.start_us + readback.dur_us <= fp.start_us
        assert by_name["dio.queue_wait"].start_us <= readback.start_us
    finally:
        storage.stop()      # flushes the access log
    with open(os.path.join(base, "logs", "access.log")) as fh:
        rows = [ln.split() for ln in fh if not ln.startswith("{")]
    (row,) = [f for f in rows if f[2] == "11"]
    work_us, fp_us = int(row[7]), int(row[8])
    cdc_us, dio_wait_us, readback_us = map(int, row[13:16])
    assert (cdc_us, readback_us) == (cdc.dur_us, readback.dur_us)
    assert 0 < cdc_us <= fp_us and 0 < readback_us
    assert dio_wait_us + readback_us + fp_us <= work_us
