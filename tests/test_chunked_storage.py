"""End-to-end chunk-level dedup through the real daemons — the north-star
path (upload → CDC → fingerprint → content-addressed chunk store →
recipe), in BOTH plugin modes:

* ``dedup_mode = cpu``      — in-process serial chunker (the referee);
* ``dedup_mode = sidecar``  — the TPU engine process over a unix socket
  (pinned to the CPU backend here; kernel bit-exactness vs the CPU path
  is covered by tests/test_pallas_kernels.py, cut-point equality by
  tests/test_chunk_cdc.py, so the sidecar's verdicts are identical by
  construction).

Covers chunk reuse (on-disk unique bytes + the dedup_bytes_saved
counter), recipe whole/range downloads, delete → chunk GC, daemon
restart → refcount rebuild + orphan GC, sidecar fail-open (down at
boot and killed mid-service), snapshot save/load, and the sidecar's
session protocol (interleaved + aborted uploads).
"""

import glob
import json
import os
import random
import signal
import socket
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

from harness import (chunk_digests, recipe_keys, upload_retry,  # noqa: E402
                     start_storage, start_tracker, wait_port)

from fastdfs_tpu.client.client import FdfsClient

HB = "heart_beat_interval = 1\nstat_report_interval = 1"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk_payloads(seed=1, shared_mb=1, tail_kb=96):
    rng = random.Random(seed)
    shared = rng.randbytes(shared_mb << 20)
    a = shared + rng.randbytes(tail_kb << 10)
    b = shared + rng.randbytes(tail_kb << 10)
    return a, b


def _recipe_for(base, fid):
    # Slab-aware: recipes may be flat .rcp sidecars OR slab records.
    remote = fid.split("/", 1)[1]
    name = os.path.basename(remote) + ".rcp"
    return name if name in recipe_keys(base) else None


def _flat_for(base, fid):
    remote = fid.split("/", 1)[1]
    hits = [p for p in glob.glob(os.path.join(
        base, "data", "**", os.path.basename(remote)), recursive=True)
        if os.path.isfile(p)]
    return hits[0] if hits else None



def _wait(pred, timeout=15.0, every=0.3):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(every)
    return pred()


def _start_sidecar(tmp_path, state_dir=None):
    sock = os.path.join(str(tmp_path), "dedup.sock")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    args = [sys.executable, "-m", "fastdfs_tpu.sidecar", "--socket", sock,
            "--platform", "cpu", "--snapshot-interval", "2"]
    if state_dir:
        args += ["--state-dir", str(state_dir)]
    proc = subprocess.Popen(args, cwd=REPO, env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    deadline = time.time() + 240  # first warmup compiles every bucket shape
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError("sidecar died during warmup")
        if os.path.exists(sock):
            try:
                s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                s.connect(sock)
                s.close()
                return proc, sock
            except OSError:
                pass
        time.sleep(0.5)
    proc.kill()
    raise TimeoutError("sidecar did not come up")


def _cluster(tmp_path, mode, sidecar_sock="", extra=HB):
    tr = start_tracker(os.path.join(str(tmp_path), "tr"))
    st = start_storage(os.path.join(str(tmp_path), "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode=mode, dedup_sidecar=sidecar_sock,
                       extra=extra)
    cli = FdfsClient([f"127.0.0.1:{tr.port}"])
    return tr, st, cli


# ---------------------------------------------------------------------------
# chunk reuse, recipe downloads, GC — both modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["cpu", "sidecar"])
def test_chunked_upload_dedups_and_gc(tmp_path, mode):
    sidecar = None
    sock = ""
    if mode == "sidecar":
        sidecar, sock = _start_sidecar(tmp_path)
    tr, st, cli = _cluster(tmp_path, mode, sock,
                           extra=HB + "\nuse_access_log = 1")
    st_base = os.path.join(str(tmp_path), "st")
    try:
        a, b = _mk_payloads()
        fa = upload_retry(cli, a, ext="bin")
        fb = cli.upload_buffer(b, ext="bin")

        # stored as recipes, not flat files
        assert _recipe_for(st_base, fa) and _recipe_for(st_base, fb)
        assert _flat_for(st_base, fa) is None

        # content-addressed store holds (far) less than the logical bytes
        unique = sum(chunk_digests(st_base).values())
        logical = len(a) + len(b)
        assert unique < logical * 0.7, (unique, logical)

        # recipe whole + range downloads
        assert cli.download_to_buffer(fa) == a
        assert cli.download_to_buffer(fb) == b
        off = (1 << 20) - 7
        assert cli.download_to_buffer(fb, offset=off, length=4321) == \
            b[off:off + 4321]

        # the daemon reports the saved bytes to the tracker
        def saved():
            rows = cli._tracker().list_storages("group1")
            return rows and rows[0].get("dedup_bytes_saved", 0) >= (1 << 19)
        assert _wait(saved), "dedup_bytes_saved never reported"

        # delete the first file: its exclusive chunks go, shared stay
        n_before = len(chunk_digests(st_base))
        cli.delete_file(fa)
        assert _wait(lambda: len(chunk_digests(st_base)) < n_before)
        assert cli.download_to_buffer(fb) == b
        with pytest.raises(Exception):
            cli.download_to_buffer(fa)

        # deleting the survivor empties the store entirely
        cli.delete_file(fb)
        assert _wait(lambda: len(chunk_digests(st_base)) == 0)
    finally:
        st.stop()       # flushes the access log
        tr.stop()
        if sidecar is not None:
            sidecar.kill()
            sidecar.wait()
    # The access log puts a chunked upload's time down to its stages in
    # either mode: the fingerprint call (column 9, `fp_us`) and the chunk
    # store's writes (column 11, `cswrite_us`) inside the dio work.
    with open(os.path.join(st_base, "logs", "access.log")) as fh:
        rows = [ln.split() for ln in fh if not ln.startswith("{")]
    uploads = [r for r in rows if r[2:4] == ["11", "0"]]
    assert len(uploads) == 2, uploads
    for row in uploads:
        work_us, fp_us, cswrite_us = int(row[7]), int(row[8]), int(row[10])
        assert 0 < fp_us <= work_us and 0 < cswrite_us <= work_us, row


def test_restart_rebuilds_refcounts_and_collects_orphans(tmp_path):
    tr, st, cli = _cluster(tmp_path, "cpu")
    st_base = os.path.join(str(tmp_path), "st")
    try:
        a, b = _mk_payloads(seed=3)
        fa = upload_retry(cli, a, ext="bin")
        fb = cli.upload_buffer(b, ext="bin")

        # plant an orphan chunk (crash leftover: written but never named
        # by any recipe)
        orphan = os.path.join(st_base, "data", "chunks", "de", "ad",
                              "de" * 20)
        os.makedirs(os.path.dirname(orphan), exist_ok=True)
        with open(orphan, "wb") as fh:
            fh.write(b"z" * 4096)

        st.stop()
        st2 = start_storage(st_base, port=st.port,
                            trackers=[f"127.0.0.1:{tr.port}"],
                            dedup_mode="cpu", extra=HB)
        try:
            wait_port(st2.port)
            # orphan GC ran at startup
            assert not os.path.exists(orphan)
            # recipes still serve
            assert cli.download_to_buffer(fa) == a
            assert cli.download_to_buffer(fb) == b
            # refcounts were rebuilt, not reset: deleting one file keeps
            # the shared chunks alive for the other
            cli.delete_file(fa)
            assert cli.download_to_buffer(fb) == b
            cli.delete_file(fb)
            assert _wait(lambda: len(chunk_digests(st_base)) == 0)
        finally:
            st2.stop()
    finally:
        st.stop()
        tr.stop()


# ---------------------------------------------------------------------------
# sidecar failure modes
# ---------------------------------------------------------------------------

def test_sidecar_down_at_boot_fails_open(tmp_path):
    # mode=sidecar with nothing listening: uploads must not block or fail,
    # they store flat.
    tr, st, cli = _cluster(tmp_path, "sidecar",
                           os.path.join(str(tmp_path), "nonexistent.sock"))
    st_base = os.path.join(str(tmp_path), "st")
    try:
        a, _ = _mk_payloads(seed=5)
        fa = upload_retry(cli, a, ext="bin")
        assert _flat_for(st_base, fa) is not None
        assert _recipe_for(st_base, fa) is None
        assert cli.download_to_buffer(fa) == a
    finally:
        st.stop()
        tr.stop()


def test_sidecar_killed_mid_service_fails_open(tmp_path):
    sidecar, sock = _start_sidecar(tmp_path)
    tr, st, cli = _cluster(tmp_path, "sidecar", sock)
    st_base = os.path.join(str(tmp_path), "st")
    try:
        a, b = _mk_payloads(seed=7)
        fa = upload_retry(cli, a, ext="bin")
        assert _recipe_for(st_base, fa) is not None  # chunked while alive

        sidecar.kill()
        sidecar.wait()
        fb = cli.upload_buffer(b, ext="bin")         # fail-open: flat
        assert _flat_for(st_base, fb) is not None
        assert _recipe_for(st_base, fb) is None
        assert cli.download_to_buffer(fa) == a
        assert cli.download_to_buffer(fb) == b
    finally:
        st.stop()
        tr.stop()
        if sidecar.poll() is None:
            sidecar.kill()


def test_sidecar_snapshot_save_load(tmp_path):
    state = tmp_path / "state"
    os.makedirs(state)
    sidecar, sock = _start_sidecar(tmp_path, state_dir=state)
    tr, st, cli = _cluster(tmp_path, "sidecar", sock)
    try:
        a, b = _mk_payloads(seed=9)
        fa = upload_retry(cli, a, ext="bin")
        _ = cli.upload_buffer(b, ext="bin")

        sidecar.send_signal(signal.SIGTERM)
        assert sidecar.wait(timeout=60) == 0

        # snapshots exist and carry no provisional state
        from fastdfs_tpu.dedup.index import ExactDigestIndex
        exact = ExactDigestIndex.load(str(state / "sidecar_exact.npz"))
        refs = [r for _, r in exact.items()]
        assert refs, "exact index snapshot is empty"
        assert all(r[0] != "(pending)" for r in refs), refs
        assert fa in {r[0] for r in refs}

        # a fresh sidecar resumes from the snapshot
        sidecar2, sock2 = _start_sidecar(tmp_path, state_dir=state)
        try:
            from fastdfs_tpu.common.protocol import StorageCmd
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.connect(sock2)
            s.sendall(struct.pack(">qBB", 0, StorageCmd.ACTIVE_TEST, 0))
            hdr = s.recv(10)
            assert hdr[8:9] == bytes([StorageCmd.RESP])
            s.close()
            near2 = np.load(str(state / "sidecar_near.npz"),
                            allow_pickle=True)
            assert int(near2["sig_spec"]) == 2
        finally:
            sidecar2.kill()
            sidecar2.wait()
    finally:
        st.stop()
        tr.stop()
        if sidecar.poll() is None:
            sidecar.kill()


# ---------------------------------------------------------------------------
# session protocol (unit level — no daemons)
# ---------------------------------------------------------------------------

def _fp_body(session, base_offset, data):
    return struct.pack(">qq", session, base_offset) + data


def test_sidecar_sessions_interleave_and_abort(tmp_path):
    from fastdfs_tpu.sidecar import DedupSidecar

    sc = DedupSidecar(str(tmp_path / "s.sock"))
    rng = np.random.RandomState(0)
    data_a = rng.randint(0, 256, 300_000, dtype=np.uint8).tobytes()
    data_b = rng.randint(0, 256, 300_000, dtype=np.uint8).tobytes()

    # interleaved segments of two concurrent uploads
    st, _ = sc._fingerprint(_fp_body(101, 0, data_a[:150_000]))
    assert st == 0
    st, _ = sc._fingerprint(_fp_body(202, 0, data_b[:150_000]))
    assert st == 0
    st, _ = sc._fingerprint(_fp_body(101, 150_000, data_a[150_000:]))
    assert st == 0
    st, _ = sc._fingerprint(_fp_body(202, 150_000, data_b[150_000:]))
    assert st == 0
    assert set(sc._sessions) == {101, 202}

    # commit A, abort B (B fell back to flat storage)
    st, _ = sc._commit(b"commitchunks 101 group1/M00/AA/AA/a.bin")
    assert st == 0
    st, _ = sc._commit(b"abort 202")
    assert st == 0
    assert sc._sessions == {}

    # only A's attribution reached the indexes; nothing provisional
    refs = {tuple(r) for _, r in sc.engine.exact.items()}
    assert refs and all(r[0] == "group1/M00/AA/AA/a.bin" for r in refs)
    assert len(sc.engine.near) == 1

    # replaying B's digests later under a new session still works
    st, _ = sc._fingerprint(_fp_body(303, 0, data_b))
    assert st == 0
    st, _ = sc._commit(b"commitchunks 303 group1/M00/BB/BB/b.bin")
    assert st == 0
    assert len(sc.engine.near) == 2


def test_sidecar_stale_session_reaped(tmp_path):
    from fastdfs_tpu import sidecar as sidecar_mod
    from fastdfs_tpu.sidecar import DedupSidecar

    sc = DedupSidecar(str(tmp_path / "s.sock"))
    rng = np.random.RandomState(1)
    data = rng.randint(0, 256, 100_000, dtype=np.uint8).tobytes()
    st, _ = sc._fingerprint(_fp_body(7, 0, data))
    assert st == 0
    sc._sessions[7].touched -= sidecar_mod._SESSION_TTL + 1
    sc._reap_stale_sessions()
    assert sc._sessions == {}
    # a commit for the reaped session is a harmless no-op
    st, _ = sc._commit(b"commitchunks 7 group1/M00/CC/CC/c.bin")
    assert st == 0
    assert len(sc.engine.near) == 0


# ---------------------------------------------------------------------------
# disk recovery keeps dedup parity
# ---------------------------------------------------------------------------

def test_recovery_rebuilds_chunked(tmp_path_factory):
    """A wiped node rebuilt from a peer must re-chunk recovered files —
    not silently store them flat and lose chunk-level dedup."""
    import shutil

    from fastdfs_tpu.client import TrackerClient
    from harness import Daemon, STORAGED, free_port

    s1_ip, s2_ip = "127.0.0.41", "127.0.0.42"
    tracker = start_tracker(tmp_path_factory.mktemp("tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    s1dir = tmp_path_factory.mktemp("s1")
    s2dir = tmp_path_factory.mktemp("s2")
    s1 = start_storage(s1dir, trackers=[taddr], dedup_mode="cpu", extra=HB,
                       ip=s1_ip)
    s2_port = free_port()
    s2 = start_storage(s2dir, port=s2_port, trackers=[taddr],
                       dedup_mode="cpu", extra=HB, ip=s2_ip)
    t = TrackerClient("127.0.0.1", tracker.port)
    cli = FdfsClient([taddr])
    try:
        assert _wait(lambda: t.list_groups() and
                     t.list_groups()[0]["active"] == 2, timeout=25)
        a, b = _mk_payloads(seed=11)
        fa = upload_retry(cli, a, ext="bin")
        fb = cli.upload_buffer(b, ext="bin")
        assert _wait(lambda: all(
            len(t.query_fetch_all(f)) == 2 for f in (fa, fb)), timeout=30), \
            "seed data never fully replicated"
        # both nodes hold recipes + shared chunks
        assert len(chunk_digests(str(s2dir))) > 0

        s2.stop()
        data_dir = os.path.join(str(s2dir), "data")
        for name in os.listdir(data_dir):
            if name == "sync":
                continue
            p = os.path.join(data_dir, name)
            shutil.rmtree(p) if os.path.isdir(p) else os.unlink(p)

        conf = os.path.join(str(s2dir), "storage.conf")
        s2 = Daemon(STORAGED, conf, s2_port, ip=s2_ip)
        assert _wait(lambda: any(
            r["ip"] == s2_ip and r.get("status") == 7
            for r in t.list_storages("group1")), timeout=60), \
            "recovered node never returned to ACTIVE"

        # the rebuilt node re-chunked: recipes exist, chunk store
        # deduplicates the shared prefix again
        assert _wait(lambda: _recipe_for(str(s2dir), fa) is not None and
                     _recipe_for(str(s2dir), fb) is not None, timeout=30), \
            "recovered files were stored flat (dedup parity lost)"
        unique = sum(chunk_digests(str(s2dir)).values())
        assert unique < len(a + b) * 0.7, (unique, len(a + b))

        # and it still serves the content (direct read from s2)
        from fastdfs_tpu.client import StorageClient
        for fid, payload in ((fa, a), (fb, b)):
            sc = StorageClient(s2_ip, s2_port)
            assert sc.download_to_buffer(fid) == payload
    finally:
        s2.stop()
        s1.stop()
        tracker.stop()


def test_sidecar_survives_stale_near_snapshot(tmp_path):
    # A spec-less (old-format) near-dup snapshot must not brick the
    # sidecar; exact state is retained, the near index restarts fresh.
    # (The files.json carries a CURRENT chunker-spec record here — a
    # stale or missing spec discards everything instead, covered by
    # test_stale_chunker_spec_state_is_discarded.)
    import json

    from fastdfs_tpu.ops.gear_cdc import CDC_SPEC_VERSION
    from fastdfs_tpu.sidecar import DedupSidecar

    state = str(tmp_path)
    with open(os.path.join(state, "sidecar_files.json"), "w") as fh:
        json.dump({"cdc_spec": CDC_SPEC_VERSION, "files": {}}, fh)
    np.savez_compressed(
        os.path.join(state, "sidecar_near.npz"),
        sigs=np.zeros((1, 64), np.uint32),
        refs=np.array(['"old"'], dtype=object), num_perms=64, bands=16)
    from fastdfs_tpu.dedup.index import ExactDigestIndex
    ex = ExactDigestIndex()
    ex.insert(b"\x01" * 20, ["group1/M00/00/00/x.bin", 0])
    ex.save(os.path.join(state, "sidecar_exact.npz"))

    sc = DedupSidecar(os.path.join(state, "s.sock"), state_dir=state)
    assert len(sc.engine.near) == 0
    assert sc.engine.exact.lookup(b"\x01" * 20) is not None


def test_appender_files_stay_flat_on_replica(tmp_path_factory):
    """Appenders are mutable and must never become recipes — not on the
    source, not via sync on the replica — or later appends fail there."""
    from fastdfs_tpu.client import TrackerClient
    from harness import free_port

    s1_ip, s2_ip = "127.0.0.51", "127.0.0.52"
    tracker = start_tracker(tmp_path_factory.mktemp("tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    s1dir = tmp_path_factory.mktemp("s1")
    s2dir = tmp_path_factory.mktemp("s2")
    s1 = start_storage(s1dir, trackers=[taddr], dedup_mode="cpu", extra=HB,
                       ip=s1_ip)
    s2 = start_storage(s2dir, port=free_port(), trackers=[taddr],
                       dedup_mode="cpu", extra=HB, ip=s2_ip)
    t = TrackerClient("127.0.0.1", tracker.port)
    cli = FdfsClient([taddr])
    try:
        assert _wait(lambda: t.list_groups() and
                     t.list_groups()[0]["active"] == 2, timeout=25)
        head = random.Random(13).randbytes(128 << 10)  # >= chunk threshold
        fid = None
        deadline = time.time() + 20
        while fid is None and time.time() < deadline:
            try:
                fid = cli.upload_appender_buffer(head, ext="log")
            except Exception:
                time.sleep(0.5)
        tail = b"appended-after-sync" * 100
        assert _wait(lambda: len(t.query_fetch_all(fid)) == 2, timeout=30)
        # both nodes hold it FLAT (no recipe), even though it is
        # chunk-eligible by size
        for d in (s1dir, s2dir):
            assert _recipe_for(str(d), fid) is None, str(d)
            assert _flat_for(str(d), fid) is not None, str(d)
        cli.append_buffer(fid, tail)
        # the append replicates and both copies serve the full content
        from fastdfs_tpu.client import StorageClient
        for ip, d in ((s1_ip, s1), (s2_ip, s2)):
            sc = StorageClient(ip, d.port)
            assert _wait(lambda: sc.download_to_buffer(fid) == head + tail,
                         timeout=20), ip
    finally:
        s2.stop()
        s1.stop()
        tracker.stop()


def test_sidecar_restart_stale_pool_retries_and_still_chunks(tmp_path):
    """After a sidecar restart the daemon's pooled connections are dead
    sockets; the RPC layer must retry each on a fresh connection so the
    next uploads still deduplicate instead of silently storing flat."""
    sidecar, sock = _start_sidecar(tmp_path,
                                   state_dir=os.path.join(str(tmp_path),
                                                          "state"))
    tr, st, cli = _cluster(tmp_path, "sidecar", sock)
    try:
        rng = np.random.RandomState(9)
        data = rng.randint(0, 256, 2 << 20, dtype=np.uint8).tobytes()
        upload_retry(cli, data, ext="bin")

        sidecar.terminate()
        sidecar.wait()
        time.sleep(0.5)
        sidecar, _ = _start_sidecar(tmp_path,
                                    state_dir=os.path.join(str(tmp_path),
                                                           "state"))

        # identical content: if the retry path works, this upload chunks
        # and every byte lands as a dedup hit
        fid = cli.upload_buffer(data, ext="bin")
        assert _wait(lambda: any(
            int(r.get("dedup_bytes_saved", 0)) >= len(data)
            for r in cli._tracker().list_storages("group1")), timeout=20), \
            "upload after sidecar restart stored flat (stale-fd retry broken)"
        # The request goes out in three parts (header, ids and cuts, the
        # segment from the caller's buffer): the retry resent all of them,
        # so the recipe covers the file and nothing was stored flat.
        base = os.path.join(str(tmp_path), "st")
        assert _recipe_for(base, fid) is not None
        assert _flat_for(base, fid) is None
        assert cli.download_to_buffer(fid) == data
        assert "storing flat" not in st.stderr_text + st.stdout_text
    finally:
        cli.close()
        st.stop()
        tr.stop()
        sidecar.kill()


def test_sidecar_stats_over_the_socket_name_the_device(tmp_path):
    """The `stats` reply read the way chip_smoke.py and the benchmark
    read it (fastdfs_tpu.sidecar.read_stats): besides the counters it
    says what the process runs on, so a reader can tell a sidecar on
    the chip from one forced onto the host path."""
    from fastdfs_tpu.sidecar import read_stats

    proc, sock = _start_sidecar(tmp_path)
    try:
        stats = read_stats(sock)
        assert stats["backend"] == "cpu"  # --platform cpu, on purpose
        assert stats["use_pallas"] is False and stats["fan_out"] == 1
        assert stats["device_count"] >= 1 and stats["device_kind"]
        assert stats["verify_host_fallbacks"] == 0
        assert stats["requests"] >= 1
    finally:
        proc.kill()
        proc.wait()
