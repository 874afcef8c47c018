"""Data-path concurrency (nio work threads + dio pools + streamed recipe
downloads — reference storage_nio.c / storage_dio.c).

The round-2 daemon was one epoll thread: a big chunked download
materialized the whole logical file before its first byte and every
other connection waited.  These tests pin the fixes: slow multi-MB
chunked downloads in flight must not stall small uploads, and the
single-threaded configuration must still work.
"""

import concurrent.futures
import random
import socket
import struct
import time

import pytest

from harness import upload_retry, start_storage, start_tracker

from fastdfs_tpu.client.client import FdfsClient
from fastdfs_tpu.common.protocol import StorageCmd

HB = "heart_beat_interval = 1\nstat_report_interval = 1"



def _slow_download(addr, fid, expect, pace_s=0.05, chunk=1 << 16):
    """Trickle-read a download, holding the response stream open for
    seconds; returns True when the bytes matched."""
    group, remote = fid.split("/", 1)
    body = (struct.pack(">qq", 0, 0) +
            group.encode().ljust(16, b"\x00") + remote.encode())
    s = socket.create_connection(addr, timeout=30)
    try:
        s.sendall(struct.pack(">qBB", len(body),
                              StorageCmd.DOWNLOAD_FILE, 0) + body)
        hdr = b""
        while len(hdr) < 10:
            got = s.recv(10 - len(hdr))
            assert got, "EOF in header"
            hdr += got
        length, _, status = struct.unpack(">qBB", hdr)
        assert status == 0, status
        received = bytearray()
        while len(received) < length:
            got = s.recv(min(chunk, length - len(received)))
            if not got:
                return False
            received += got
            time.sleep(pace_s)  # trickle: keep the stream open
        return bytes(received) == expect
    finally:
        s.close()


def test_slow_chunked_download_does_not_block_uploads(tmp_path):
    tr = start_tracker(str(tmp_path / "tr"))
    st = start_storage(str(tmp_path / "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu", extra=HB)
    cli = FdfsClient([f"127.0.0.1:{tr.port}"])
    try:
        rng = random.Random(21)
        big = rng.randbytes(24 << 20)  # chunked (threshold 64 KB)
        fid_big = upload_retry(cli, big, ext="bin")
        addr = ("127.0.0.1", st.port)

        with concurrent.futures.ThreadPoolExecutor(max_workers=4) as ex:
            # three trickle-readers hold chunked downloads open for
            # several seconds each
            downloads = [ex.submit(_slow_download, addr, fid_big, big,
                                   0.01, 1 << 17) for _ in range(3)]
            time.sleep(0.5)  # ensure the streams are mid-flight
            # concurrent small uploads must stay fast
            lat = []
            for i in range(8):
                small = rng.randbytes(8 << 10)
                t0 = time.perf_counter()
                fid = cli.upload_buffer(small, ext="bin")
                lat.append(time.perf_counter() - t0)
                assert cli.download_to_buffer(fid) == small
            assert all(f.result(timeout=120) for f in downloads)
        worst = max(lat)
        assert worst < 2.0, f"small upload stalled {worst:.2f}s behind " \
                            "an in-flight chunked download"
    finally:
        st.stop()
        tr.stop()


@pytest.mark.parametrize("threads", [1, 4])
def test_work_thread_configs(tmp_path, threads):
    tr = start_tracker(str(tmp_path / "tr"))
    st = start_storage(str(tmp_path / "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu",
                       extra=HB + f"\nwork_threads = {threads}\n"
                                  "disk_writer_threads = 1\n")
    cli = FdfsClient([f"127.0.0.1:{tr.port}"])
    try:
        rng = random.Random(threads)
        payloads = [rng.randbytes(200 << 10) for _ in range(4)]
        fids = [upload_retry(cli, b, ext="bin") for b in payloads]
        for fid, b in zip(fids, payloads):
            assert cli.download_to_buffer(fid) == b
        cli.delete_file(fids[0])
        assert cli.download_to_buffer(fids[1]) == payloads[1]
    finally:
        st.stop()
        tr.stop()


@pytest.mark.parametrize("pool, clients", [
    ("", 6),                                # the derived dio pool
    ("\ndisk_writer_threads = 16", 12),     # pinned wider than the clients
], ids=["derived", "wide"])
def test_parallel_uploads_all_land(tmp_path, pool, clients):
    # many concurrent client connections across the nio threads, and as
    # many chunked uploads past the receive stage as the dio pool is wide
    tr = start_tracker(str(tmp_path / "tr"))
    st = start_storage(str(tmp_path / "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu", extra=HB + pool)
    taddr = f"127.0.0.1:{tr.port}"
    try:
        upload_retry(FdfsClient([taddr]), b"warm" * 100, ext="bin")
        rng = random.Random(33)
        payloads = [rng.randbytes((64 << 10) + i * 1111) for i in range(12)]

        def one(data):
            c = FdfsClient([taddr])   # own connection per thread
            fid = c.upload_buffer(data, ext="bin")
            return fid, c.download_to_buffer(fid) == data

        with concurrent.futures.ThreadPoolExecutor(clients) as ex:
            results = list(ex.map(one, payloads))
        assert all(ok for _, ok in results)
        assert len({fid for fid, _ in results}) == len(payloads)
    finally:
        st.stop()
        tr.stop()


def test_delete_during_chunked_download_completes(tmp_path):
    # An in-flight chunked download pins its chunks (ChunkStore stream
    # pins): deleting the file mid-stream must not truncate the reader —
    # the POSIX open-fd guarantee flat files get from sendfile.
    import glob
    import os

    tr = start_tracker(str(tmp_path / "tr"))
    st = start_storage(str(tmp_path / "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu", extra=HB)
    cli = FdfsClient([f"127.0.0.1:{tr.port}"])
    try:
        rng = random.Random(55)
        big = rng.randbytes(8 << 20)
        fid = upload_retry(cli, big, ext="bin")
        addr = ("127.0.0.1", st.port)
        with concurrent.futures.ThreadPoolExecutor(max_workers=1) as ex:
            dl = ex.submit(_slow_download, addr, fid, big, 0.01, 1 << 17)
            time.sleep(0.3)          # stream mid-flight
            cli.delete_file(fid)     # concurrent delete
            assert dl.result(timeout=120), \
                "chunked download truncated by concurrent delete"
        # once the stream finished, the deferred chunk GC completes

        def chunks_left():
            # Slab-aware inventory: flat files AND live slab records.
            from harness import chunk_digests
            return chunk_digests(str(tmp_path / "st"))
        deadline = time.time() + 10
        while time.time() < deadline and chunks_left():
            time.sleep(0.3)
        assert not chunks_left(), "pinned chunks never collected"
    finally:
        st.stop()
        tr.stop()


def _recv_exact(sock, n, timeout=10.0):
    sock.settimeout(timeout)
    buf = b""
    while len(buf) < n:
        part = sock.recv(n - len(buf))
        if not part:
            raise ConnectionError(f"peer closed after {len(buf)}/{n} bytes")
        buf += part
    return buf


def _active_test(sock):
    """One ACTIVE_TEST round-trip; proves the conn was adopted by a nio
    thread (the accept-time cap reads the adopted-conn counter)."""
    sock.sendall(struct.pack(">qBB", 0, StorageCmd.ACTIVE_TEST, 0))
    hdr = _recv_exact(sock, 10)
    assert hdr[9] == 0, f"active test failed: status {hdr[9]}"


def test_max_connections_cap(tmp_path):
    """Accept past max_connections must refuse politely: one EBUSY
    response header, then close — and closing a held conn frees a slot
    (reference: fast_task_queue.c pool exhaustion / max_connections)."""
    tr = start_tracker(str(tmp_path / "tr"))
    st = start_storage(str(tmp_path / "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu",
                       extra=HB + "\nmax_connections = 3\nwork_threads = 4\n")
    cli = FdfsClient([f"127.0.0.1:{tr.port}"], use_pool=False)
    addr = ("127.0.0.1", st.port)
    held = []
    try:
        fid = upload_retry(cli, b"cap" * 100, ext="bin")
        time.sleep(0.5)  # let the server reap the upload's closed conn
        for _ in range(3):
            s = socket.create_connection(addr, timeout=10)
            _active_test(s)
            held.append(s)
        # Fourth conn: the daemon answers an EBUSY header and closes.
        over = socket.create_connection(addr, timeout=10)
        hdr = _recv_exact(over, 10)
        assert hdr[8] == 100 and hdr[9] == 16, f"expected EBUSY resp: {hdr!r}"
        assert over.recv(1) == b""  # and then EOF
        over.close()
        # Freeing one slot lets a new connection in (HUP reap is prompt,
        # but poll a little: the close must cross the loopback first).
        held.pop().close()
        deadline = time.time() + 10
        while True:
            s = socket.create_connection(addr, timeout=10)
            hdr = _recv_or_none(s)
            if hdr is None:  # no unsolicited EBUSY: a real slot
                _active_test(s)
                held.append(s)
                break
            s.close()
            assert time.time() < deadline, "slot never freed after close"
            time.sleep(0.2)
        # The cap must not break normal service once conns drop.
        for s in held:
            s.close()
        held.clear()
        deadline = time.time() + 10
        while True:
            try:
                assert cli.download_to_buffer(fid) == b"cap" * 100
                break
            except Exception:
                assert time.time() < deadline
                time.sleep(0.2)
    finally:
        for s in held:
            s.close()
        st.stop()
        tr.stop()


def _recv_or_none(sock, timeout=0.5):
    """Read an unsolicited 10-byte refusal header if one arrives within
    the timeout; None means the server kept the conn (a granted slot)."""
    sock.settimeout(timeout)
    try:
        buf = sock.recv(10)
    except socket.timeout:
        return None
    return buf or b""
