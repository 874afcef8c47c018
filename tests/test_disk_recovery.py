"""Integration: disk recovery (SURVEY.md §2.2 storage_disk_recovery).

Reference semantics under test:
- a storage that boots with a wiped data dir but prior sync state fetches
  the one-path binlog from a group peer (FETCH_ONE_PATH_BINLOG 26) and
  re-downloads every listed file (storage_disk_recovery_start);
- while rebuilding it is held out of read routing (upstream: RECOVERY
  status; here: the tracker's re-enter-sync handshake) and promoted back
  to ACTIVE only when done;
- files deleted since their binlog record are skipped, not errors.
"""

import os
import shutil
import time

import pytest

from fastdfs_tpu.client import FdfsClient, StorageClient, TrackerClient
from fastdfs_tpu.client.conn import StatusError
from fastdfs_tpu.common.fileid import decode_file_id
from fastdfs_tpu.common.protocol import StorageStatus
from tests.harness import Daemon, STORAGED, free_port, make_storage_conf, \
    start_storage, start_tracker

HB = "heart_beat_interval = 1\nstat_report_interval = 1"
S1_IP, S2_IP = "127.0.0.31", "127.0.0.32"


def _wait(cond, timeout=25, interval=0.3):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(interval)
    return None


def test_wiped_storage_rebuilds_from_peer(tmp_path_factory):
    tracker = start_tracker(tmp_path_factory.mktemp("tracker"))
    taddr = f"127.0.0.1:{tracker.port}"
    s1dir = tmp_path_factory.mktemp("s1")
    s2dir = tmp_path_factory.mktemp("s2")
    s1 = start_storage(s1dir, trackers=[taddr], extra=HB, ip=S1_IP)
    s2_port = free_port()
    s2 = start_storage(s2dir, port=s2_port, trackers=[taddr], extra=HB,
                       ip=S2_IP)
    t = TrackerClient("127.0.0.1", tracker.port)
    try:
        assert _wait(lambda: t.list_groups() and
                     t.list_groups()[0]["active"] == 2)
        fdfs = FdfsClient(taddr)
        # Seed data sourced from BOTH members, then delete a couple.
        fids = []
        for i in range(12):
            data = bytes([i]) * (200 + 97 * i)
            fids.append((fdfs.upload_buffer(data, ext="bin"), data))
        deleted = [fids.pop(), fids.pop()]
        for fid, _ in deleted:
            fdfs.delete_file(fid)
        # Wait until every survivor is fully replicated (2 replicas).
        assert _wait(lambda: all(
            len(t.query_fetch_all(fid)) == 2 for fid, _ in fids)), \
            "seed data never fully replicated"

        # Kill s2 and WIPE its data dir (keep sync state: marks survive in
        # <base>/data/sync — the wipe nukes payload dirs + init flag).
        s2.stop()
        data_dir = os.path.join(str(s2dir), "data")
        for name in os.listdir(data_dir):
            if name == "sync":
                continue
            p = os.path.join(data_dir, name)
            shutil.rmtree(p) if os.path.isdir(p) else os.unlink(p)

        # Restart s2 on the same port: it must detect the wipe and rebuild.
        conf = os.path.join(str(s2dir), "storage.conf")
        s2 = Daemon(STORAGED, conf, s2_port, ip=S2_IP)

        # While recovering, the tracker must keep it out of read routing.
        st = _wait(lambda: {x["ip"]: x["status"]
                            for x in t.list_storages("group1")}.get(S2_IP))
        assert st is not None
        # Eventually it returns ACTIVE with everything restored.
        assert _wait(lambda: {x["ip"]: x["status"]
                              for x in t.list_storages("group1")}.get(S2_IP)
                     == StorageStatus.ACTIVE, timeout=30), \
            "recovering node never promoted back to ACTIVE"

        # ACTIVE promotion and the final file landing can race by a
        # poll or two under suite load (1-core box): retry the full
        # byte-for-byte sweep instead of failing on the first ENOENT.
        def _all_recovered():
            with StorageClient(S2_IP, s2_port) as c:
                try:
                    return all(c.download_to_buffer(fid) == data
                               for fid, data in fids)
                except StatusError:
                    return False

        assert _wait(_all_recovered, timeout=90), \
            f"not all {len(fids)} files recovered byte-identical"
        with StorageClient(S2_IP, s2_port) as c:
            # Deleted files stay dead.
            for fid, _ in deleted:
                with pytest.raises(StatusError):
                    c.download_to_buffer(fid)
        # Marker removed: a subsequent clean restart must NOT re-recover.
        assert not os.path.exists(os.path.join(data_dir, ".recovery"))
    finally:
        for d in (s1, s2, tracker):
            d.stop()


def test_fetch_one_path_binlog_rpc(tmp_path_factory):
    """Direct probe of cmd 26: the response lists this path's records."""
    import socket
    from fastdfs_tpu.common.protocol import StorageCmd, long2buff, \
        pack_group_name

    storage = start_storage(tmp_path_factory.mktemp("sb"), group="group1")
    try:
        with StorageClient("127.0.0.1", storage.port) as c:
            fid1 = c.upload_buffer(b"alpha")
            fid2 = c.upload_buffer(b"beta")
        body = pack_group_name("group1") + bytes([0])
        with socket.create_connection(("127.0.0.1", storage.port),
                                      timeout=5) as sk:
            sk.sendall(long2buff(len(body)) +
                       bytes([StorageCmd.FETCH_ONE_PATH_BINLOG, 0]) + body)
            hdr = b""
            while len(hdr) < 10:
                hdr += sk.recv(10 - len(hdr))
            assert hdr[9] == 0
            length = int.from_bytes(hdr[:8], "big")
            resp = b""
            while len(resp) < length:
                resp += sk.recv(length - len(resp))
        text = resp.decode()
        for fid in (fid1, fid2):
            remote = fid.split("/", 1)[1]
            assert remote in text
        # bad store path index rejected
        with socket.create_connection(("127.0.0.1", storage.port),
                                      timeout=5) as sk:
            bad = pack_group_name("group1") + bytes([9])
            sk.sendall(long2buff(len(bad)) +
                       bytes([StorageCmd.FETCH_ONE_PATH_BINLOG, 0]) + bad)
            hdr = b""
            while len(hdr) < 10:
                hdr += sk.recv(10 - len(hdr))
            assert hdr[9] == 22
    finally:
        storage.stop()


def test_whole_group_restart_holds_wiped_node(tmp_path_factory):
    """Regression: when the wiped node and its peer restart together, the
    wiped node must wait in WAIT_SYNC for a live source — never go ACTIVE
    with an empty disk just because no peer was ACTIVE at query time."""
    tracker = start_tracker(tmp_path_factory.mktemp("tw"))
    taddr = f"127.0.0.1:{tracker.port}"
    s1dir = tmp_path_factory.mktemp("ws1")
    s2dir = tmp_path_factory.mktemp("ws2")
    s1_port, s2_port = free_port(), free_port()
    s1 = start_storage(s1dir, port=s1_port, trackers=[taddr], extra=HB,
                       ip=S1_IP)
    s2 = start_storage(s2dir, port=s2_port, trackers=[taddr], extra=HB,
                       ip=S2_IP)
    t = TrackerClient("127.0.0.1", tracker.port)
    try:
        assert _wait(lambda: t.list_groups() and
                     t.list_groups()[0]["active"] == 2)
        fdfs = FdfsClient(taddr)
        fids = [(fdfs.upload_buffer(f"wg {i}".encode()), f"wg {i}".encode())
                for i in range(6)]
        assert _wait(lambda: all(
            len(t.query_fetch_all(fid)) == 2 for fid, _ in fids))
        # Stop BOTH; wipe s2; restart s2 FIRST (no live source exists).
        s1.stop()
        s2.stop()
        data_dir = os.path.join(str(s2dir), "data")
        for name in os.listdir(data_dir):
            if name == "sync":
                continue
            p = os.path.join(data_dir, name)
            shutil.rmtree(p) if os.path.isdir(p) else os.unlink(p)
        s2 = Daemon(STORAGED, os.path.join(str(s2dir), "storage.conf"),
                    s2_port, ip=S2_IP)
        # With no live source, s2 must hold in WAIT_SYNC/SYNCING (the
        # tracker may still believe the dead peer is ACTIVE for a beat
        # timeout), but NEVER ACTIVE.
        time.sleep(2.5)
        st = {x["ip"]: x["status"] for x in t.list_storages("group1")}
        assert st[S2_IP] in (StorageStatus.WAIT_SYNC,
                             StorageStatus.SYNCING), st
        # Bring the source back: recovery proceeds, s2 ends ACTIVE + whole.
        s1 = Daemon(STORAGED, os.path.join(str(s1dir), "storage.conf"),
                    s1_port, ip=S1_IP)
        assert _wait(lambda: {x["ip"]: x["status"]
                              for x in t.list_storages("group1")}.get(S2_IP)
                     == StorageStatus.ACTIVE, timeout=30)
        with StorageClient(S2_IP, s2_port) as c:
            for fid, data in fids:
                assert c.download_to_buffer(fid) == data
    finally:
        for d in (s1, s2, tracker):
            d.stop()


def test_chunk_aware_recovery_pulls_only_unique_bytes(tmp_path_factory):
    """A wiped node with chunk dedup rebuilds recipe-stored files by
    pulling recipes + chunk payloads (FETCH_RECIPE 128 / FETCH_CHUNK
    129); duplicate chunks cross the wire once, not once per file, and
    no full-file DOWNLOAD_FILE is needed for chunked content."""
    import random
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from access_log_stages import aggregate

    tracker = start_tracker(tmp_path_factory.mktemp("catr"))
    taddr = f"127.0.0.1:{tracker.port}"
    s1dir = tmp_path_factory.mktemp("cas1")
    s2dir = tmp_path_factory.mktemp("cas2")
    extra = HB + "\nuse_access_log = true"
    ips = ("127.0.0.33", "127.0.0.34")
    s1 = start_storage(s1dir, trackers=[taddr], extra=extra, ip=ips[0],
                       dedup_mode="cpu")
    s2_port = free_port()
    s2 = start_storage(s2dir, port=s2_port, trackers=[taddr], extra=extra,
                       ip=ips[1], dedup_mode="cpu")
    t = TrackerClient("127.0.0.1", tracker.port)
    try:
        assert _wait(lambda: t.list_groups() and
                     t.list_groups()[0]["active"] == 2)
        fdfs = FdfsClient(taddr)
        rng = random.Random(41)
        shared = rng.randbytes(1 << 20)
        files = []
        for i in range(4):  # 4 files sharing a 1MB prefix (dup-heavy)
            data = shared + rng.randbytes(128 << 10)
            files.append((fdfs.upload_buffer(data, ext="bin"), data))
        assert _wait(lambda: all(
            len(t.query_fetch_all(fid)) == 2 for fid, _ in files),
            timeout=60), "seed data never fully replicated"

        s2.stop()
        data_dir = os.path.join(str(s2dir), "data")
        for name in os.listdir(data_dir):
            if name == "sync":
                continue
            p = os.path.join(data_dir, name)
            shutil.rmtree(p) if os.path.isdir(p) else os.unlink(p)
        # truncate s1's access log so the assertion sees only recovery
        open(os.path.join(str(s1dir), "logs", "access.log"), "w").close()

        conf = os.path.join(str(s2dir), "storage.conf")
        s2 = Daemon(STORAGED, conf, s2_port, ip=ips[1])
        assert _wait(lambda: all(
            len(t.query_fetch_all(fid)) == 2 for fid, _ in files),
            timeout=60), "recovery never completed"

        # byte-identical reads directly from the rebuilt node
        with StorageClient(ips[1], s2_port) as sc:
            for fid, data in files:
                assert sc.download_to_buffer(fid) == data
    finally:
        s2.stop()
        s1.stop()
        tracker.stop()

    agg = aggregate(os.path.join(str(s1dir), "logs", "access.log"))
    assert agg.get("cmd128", agg.get("fetch_recipe", {})).get("count", 0) >= 4
    chunk_rows = agg.get("cmd129", agg.get("fetch_chunk", {}))
    assert chunk_rows.get("count", 0) > 0
    # wire discipline: chunk payload bytes served ~ unique bytes, far
    # below the 4 * (1MB + 128KB) logical total; and no full-file
    # download was needed for the chunked content
    logical = sum(len(d) for _, d in files)
    assert chunk_rows.get("bytes", 0) < logical * 0.55, \
        (chunk_rows.get("bytes"), logical)
    assert agg.get("download", {}).get("count", 0) == 0


def test_sidecar_mode_recovery_reindexes_near_dups(tmp_path_factory):
    """A sidecar-mode rebuild must re-register recovered files with its
    (fresh) dedup engine: after wiping BOTH the data path and the
    sidecar state, NEAR_DUPS on the rebuilt node still reports the
    recovered neighbours (ReindexFromRecipe feeds the bytes, read from
    the recipe just stored, back through the plugin)."""
    import random
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_chunked_storage import _start_sidecar
    from fastdfs_tpu.sidecar import read_stats

    tracker = start_tracker(tmp_path_factory.mktemp("nrtr"))
    taddr = f"127.0.0.1:{tracker.port}"
    s1dir = tmp_path_factory.mktemp("nrs1")
    s2dir = tmp_path_factory.mktemp("nrs2")
    sc1_dir = tmp_path_factory.mktemp("nrsc1")
    sc2_dir = tmp_path_factory.mktemp("nrsc2")
    os.makedirs(os.path.join(str(sc1_dir), "state"), exist_ok=True)
    os.makedirs(os.path.join(str(sc2_dir), "state"), exist_ok=True)
    sc1, sock1 = _start_sidecar(sc1_dir, state_dir=os.path.join(
        str(sc1_dir), "state"))
    sc2, sock2 = _start_sidecar(sc2_dir, state_dir=os.path.join(
        str(sc2_dir), "state"))
    ips = ("127.0.0.35", "127.0.0.36")
    s1 = start_storage(s1dir, trackers=[taddr], extra=HB, ip=ips[0],
                       dedup_mode="sidecar", dedup_sidecar=sock1)
    s2_port = free_port()
    s2 = start_storage(s2dir, port=s2_port, trackers=[taddr], extra=HB,
                       ip=ips[1], dedup_mode="sidecar", dedup_sidecar=sock2)
    t = TrackerClient("127.0.0.1", tracker.port)
    try:
        assert _wait(lambda: t.list_groups() and
                     t.list_groups()[0]["active"] == 2)
        fdfs = FdfsClient(taddr)
        rng = random.Random(47)
        shared = rng.randbytes(1 << 20)
        da = shared + rng.randbytes(64 << 10)
        db = shared + rng.randbytes(64 << 10)
        fa = fdfs.upload_buffer(da, ext="bin")
        fb = fdfs.upload_buffer(db, ext="bin")
        assert _wait(lambda: all(
            len(t.query_fetch_all(f)) == 2 for f in (fa, fb)), timeout=60)

        # Wipe s2's data AND its sidecar's state: the rebuilt node's
        # engine starts empty, so only recovery-time reindexing can
        # repopulate it.
        s2.stop()
        sc2.kill()
        sc2.wait()
        data_dir = os.path.join(str(s2dir), "data")
        for name in os.listdir(data_dir):
            if name == "sync":
                continue
            p = os.path.join(data_dir, name)
            shutil.rmtree(p) if os.path.isdir(p) else os.unlink(p)
        shutil.rmtree(os.path.join(str(sc2_dir), "state"))
        os.makedirs(os.path.join(str(sc2_dir), "state"))
        sc2, _ = _start_sidecar(sc2_dir, state_dir=os.path.join(
            str(sc2_dir), "state"))
        before = read_stats(sock2)["reindex_bytes"]

        conf = os.path.join(str(s2dir), "storage.conf")
        s2 = Daemon(STORAGED, conf, s2_port, ip=ips[1])
        assert _wait(lambda: all(
            len(t.query_fetch_all(f)) == 2 for f in (fa, fb)), timeout=90), \
            "recovery never completed"

        # the REBUILT node's own near index knows the recovered pair
        with StorageClient(ips[1], s2_port) as sc:
            got = _wait(lambda: any(
                r == fb for r, _ in sc.near_dups(fa)) or None, timeout=30)
            assert got, "recovered files missing from the near-dup index"
        # Both files are over the chunk threshold, so both came back from
        # their recipes, and the re-index read every byte of each.
        assert read_stats(sock2)["reindex_bytes"] - before == len(da) + len(db)
    finally:
        s2.stop()
        s1.stop()
        tracker.stop()
        sc1.kill()
        sc2.kill()
