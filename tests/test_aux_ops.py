"""Aux subsystems (SURVEY.md §5): access log, stat persistence, identity
changelog / IP-changed dealer, storage IDs, status file, monitor CLI."""

import io
import os
import time
from contextlib import redirect_stdout

import pytest

from fastdfs_tpu.cli import main as cli_main
from fastdfs_tpu.client import FdfsClient, StorageClient, TrackerClient
from tests.harness import Daemon, STORAGED, free_port, start_storage, \
    start_tracker

HB = "heart_beat_interval = 1\nstat_report_interval = 1"


def _wait(cond, timeout=20, interval=0.25):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(interval)
    return None


def test_access_log_lines(tmp_path_factory):
    base = tmp_path_factory.mktemp("al")
    storage = start_storage(base, extra="use_access_log = 1")
    try:
        with StorageClient("127.0.0.1", storage.port) as c:
            fid = c.upload_buffer(b"logged " * 100)
            assert c.download_to_buffer(fid)
    finally:
        storage.stop()  # flushes + closes the log
    log_path = os.path.join(str(base), "logs", "access.log")
    assert os.path.exists(log_path)
    lines = open(log_path).read().strip().splitlines()
    # a request that recorded stages is followed by its intervals, one
    # compact JSON token (tests/test_stage_intervals.py reads those)
    stage_lines = [ln for ln in lines if ln.startswith("{")]
    assert stage_lines and all(" " not in ln for ln in stage_lines)
    lines = [ln for ln in lines if not ln.startswith("{")]
    assert len(lines) >= 2  # upload + download
    # "<ts> <ip> <cmd> <status> <bytes> <cost_us> <recv_us> <work_us>
    #  <fp_us> <fp_lock_us> <cswrite_us> <binlog_us> <req_bytes>
    #  <cdc_us> <dio_wait_us> <readback_us> <negotiate_us> <present_us>
    #  <verify_us> <recipe_us> <reindex_us>" —
    # per-stage split (SURVEY.md §5): recv = body window, work = dio,
    # then the chunked-upload splits inside the work window; the last
    # three were appended after req_bytes (native chunker inside fp,
    # dio queue wait and tmp-file read-back inside work), the last five
    # after those (the negotiated upload's stages: 0 on these requests).
    for line in lines:
        (ts, ip, cmd, status, nbytes, cost, recv_us, work_us,
         fp_us, fp_lock_us, cswrite_us, binlog_us, req_bytes,
         cdc_us, dio_wait_us, readback_us, *negotiated) = line.split()
        assert negotiated == ["0"] * 5
        assert int(ts) > 0 and ip == "127.0.0.1"
        assert int(status) == 0 and int(cost) >= 0
        assert int(recv_us) >= 0 and int(work_us) >= 0
        assert int(recv_us) <= int(cost) and int(work_us) <= int(cost)
        assert int(fp_lock_us) <= int(fp_us) <= int(work_us)
        assert int(cswrite_us) >= 0 and int(binlog_us) >= 0
        assert int(req_bytes) >= 0
        assert 0 <= int(cdc_us) <= int(fp_us)
        assert 0 <= int(dio_wait_us) <= int(work_us)
        assert 0 <= int(readback_us) <= int(work_us)
    cmds = {int(l.split()[2]) for l in lines}
    assert 11 in cmds and 14 in cmds  # UPLOAD_FILE, DOWNLOAD_FILE


def test_stats_survive_restart(tmp_path_factory):
    base = tmp_path_factory.mktemp("st")
    port = free_port()
    storage = start_storage(base, port=port)
    try:
        with StorageClient("127.0.0.1", port) as c:
            for i in range(5):
                c.upload_buffer(f"stat {i}".encode())
        storage.stop()  # persists counters
        storage = Daemon(STORAGED, os.path.join(str(base), "storage.conf"),
                         port)
        # Counters reloaded: visible via a tracker-less probe is not
        # possible (stats ride beats), so read the stat file directly.
        stat = open(os.path.join(str(base), "data",
                                 "storage_stat.dat")).read().split()
        assert int(stat[0]) == 5 and int(stat[1]) == 5  # total/success upload
    finally:
        storage.stop()


def test_ip_changed_dealer(tmp_path_factory):
    """A storage restarted with a NEW IP keeps its cluster identity: the
    tracker renames the node (status, sync vectors) instead of treating it
    as a fresh member, and peers learn via the changelog."""
    tracker = start_tracker(tmp_path_factory.mktemp("ict"))
    taddr = f"127.0.0.1:{tracker.port}"
    base = tmp_path_factory.mktemp("ics")
    port = free_port()
    s = start_storage(base, port=port, trackers=[taddr], extra=HB,
                      ip="127.0.0.51")
    t = TrackerClient("127.0.0.1", tracker.port)
    try:
        assert _wait(lambda: t.list_groups() and
                     t.list_groups()[0]["active"] == 1)
        s.stop()
        # Same base dir (identity file says 127.0.0.51), new bind IP.
        conf = os.path.join(str(base), "storage.conf")
        text = open(conf).read().replace("bind_addr = 127.0.0.51",
                                         "bind_addr = 127.0.0.52")
        open(conf, "w").write(text)
        s = Daemon(STORAGED, conf, port, ip="127.0.0.52")
        assert _wait(lambda: any(
            x["ip"] == "127.0.0.52" for x in t.list_storages("group1")))
        storages = t.list_storages("group1")
        # Renamed, not duplicated: exactly one member.
        assert len(storages) == 1 and storages[0]["ip"] == "127.0.0.52"
        # Changelog records the move.
        log = open(os.path.join(tracker_base(tracker), "data",
                                "changelog.dat")).read()
        assert "127.0.0.51" in log and "127.0.0.52" in log
    finally:
        s.stop()
        tracker.stop()


def tracker_base(tracker):
    # harness writes tracker.conf inside the base dir; recover it from conf
    import re
    # conf path: the Daemon stores no base; read from its process args
    with open(f"/proc/{tracker.proc.pid}/cmdline", "rb") as fh:
        conf = fh.read().split(b"\0")[1].decode()
    for line in open(conf):
        if line.startswith("base_path"):
            return line.split("=", 1)[1].strip()
    raise AssertionError("no base_path in tracker conf")


def test_storage_ids_in_monitor(tmp_path_factory):
    base = tmp_path_factory.mktemp("sid")
    ids_file = os.path.join(str(base), "storage_ids.conf")
    open(ids_file, "w").write("100001 group1 127.0.0.53\n")
    tracker = start_tracker(base, extra=f"use_storage_id = 1\n"
                                        f"storage_ids_filename = {ids_file}")
    s = start_storage(tmp_path_factory.mktemp("sids"),
                      trackers=[f"127.0.0.1:{tracker.port}"], extra=HB,
                      ip="127.0.0.53")
    try:
        with TrackerClient("127.0.0.1", tracker.port) as t:
            assert _wait(lambda: t.list_storages("group1"))
            st = t.list_storages("group1")[0]
            assert st["id"] == "100001"
    finally:
        s.stop()
        tracker.stop()


def test_tracker_status_file(tmp_path_factory):
    base = tmp_path_factory.mktemp("tsf")
    tracker = start_tracker(base)  # save_interval=2 in harness
    try:
        path = os.path.join(str(base), "data", "tracker_status.dat")
        assert _wait(lambda: os.path.exists(path), timeout=10)
        text = open(path).read()
        assert "am_leader=1" in text and "leader=127.0.0.1:" in text
    finally:
        tracker.stop()


def test_cli_tools_end_to_end(tmp_path_factory, tmp_path):
    tracker = start_tracker(tmp_path_factory.mktemp("clit"))
    taddr = f"127.0.0.1:{tracker.port}"
    s = start_storage(tmp_path_factory.mktemp("clis"), trackers=[taddr],
                      extra=HB)
    try:
        with TrackerClient("127.0.0.1", tracker.port) as t:
            assert _wait(lambda: t.list_groups() and
                         t.list_groups()[0]["active"] == 1)
        local = tmp_path / "payload.bin"
        local.write_bytes(b"cli payload " * 50)

        def run(*args):
            out = io.StringIO()
            with redirect_stdout(out):
                rc = cli_main(list(args))
            return rc, out.getvalue()

        rc, fid = run("upload", taddr, str(local))
        assert rc == 0
        fid = fid.strip()
        rc, out = run("file_info", taddr, fid)
        assert rc == 0 and "source ip" in out
        rc, out = run("monitor", taddr)
        assert rc == 0 and "group1" in out
        rc, out = run("tracker_status", taddr)
        assert rc == 0 and "am_leader" in out
        dest = tmp_path / "back.bin"
        rc, _ = run("download", taddr, fid, str(dest))
        assert rc == 0 and dest.read_bytes() == local.read_bytes()
        rc, _ = run("delete", taddr, fid)
        assert rc == 0
        rc, out = run("test", taddr)
        assert rc == 0 and "delete: OK" in out
    finally:
        s.stop()
        tracker.stop()


def test_log_rotation_by_size(tmp_path_factory):
    """logger.c parity: the file sink rotates when it exceeds
    log_rotate_size (rotated copies keep a timestamp suffix)."""
    import glob

    base = tmp_path_factory.mktemp("rot")
    extra = "log_file = storaged.log\nlog_rotate_size = 256"
    port = free_port()
    # each boot writes a few hundred bytes of INFO; with a 256-byte limit
    # every restart's first write must rotate the previous file out
    for _ in range(3):
        storage = start_storage(base, port=port, extra=extra)
        with StorageClient("127.0.0.1", port) as c:
            c.upload_buffer(b"rotate me")
        storage.stop()
    logs = glob.glob(os.path.join(str(base), "logs", "storaged.log*"))
    assert any(p.endswith("storaged.log") for p in logs)
    rotated = [p for p in logs if not p.endswith("storaged.log")]
    assert rotated, f"no rotated log files in {logs}"
    for p in rotated:  # rotated names carry the timestamp suffix
        assert os.path.basename(p).startswith("storaged.log.")
