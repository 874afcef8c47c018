"""A store path's dio pool: how many workers the daemon starts
(``disk_writer_threads``: 0 = derived from the host's cores and the store
paths, a positive value pins it; the ``dio.workers`` gauge reads the
node's total), and that the pool's width reaches the sidecar: as many
uploads inside the fingerprint RPC at once as there are workers, each
on a connection the daemon keeps afterwards.

The rule itself, as a pure function of (configured, cores, store paths),
is checked in ``native/tests/common_test.cc`` (``TestDioWorkersPerPath``).
"""

from __future__ import annotations

import hashlib
import os
import random
import socket
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from fastdfs_tpu import monitor as M
from fastdfs_tpu.client.storage_client import StorageClient
from fastdfs_tpu.common.protocol import HEADER_SIZE, StorageCmd, unpack_header
from fastdfs_tpu.sidecar import DedupSidecar
from harness import recipe_keys, start_storage

# native/common/workers.h: the bounds of the derived number.
FLOOR, CAP = 2, 64


def dio_workers(tmp_path, extra: str, paths: int = 1) -> int:
    base = str(tmp_path)
    for i in range(1, paths):
        os.makedirs(os.path.join(base, f"sp{i}"))
        extra += f"\nstore_path{i} = {base}/sp{i}"
    if paths > 1:
        extra += f"\nstore_path_count = {paths}"
    st = start_storage(base, extra=extra)
    try:
        with StorageClient(st.ip, st.port) as sc:
            return M.decode_registry(sc.stat())["gauges"]["dio.workers"]
    finally:
        st.stop()


@pytest.mark.parametrize("pin, paths, total", [(1, 1, 1), (3, 1, 3),
                                               (3, 2, 6)])
def test_pinned_pool_is_taken_as_it_stands(tmp_path, pin, paths, total):
    assert dio_workers(tmp_path, f"disk_writer_threads = {pin}",
                       paths) == total


def test_derived_pool_follows_the_host_and_paths_divide_it(tmp_path):
    absent = dio_workers(tmp_path / "a", "")
    assert dio_workers(tmp_path / "z", "disk_writer_threads = 0") == absent
    assert FLOOR <= absent <= CAP
    # Two store paths share the node's total: each pool gets half of it
    # (never under the floor), so the node does not start twice as many.
    assert dio_workers(tmp_path / "two", "", paths=2) \
        == 2 * max(FLOOR, absent // 2)


class SleepySidecar:
    """The sidecar's side of the fingerprint RPC with the chip replaced by
    a sleep: DEDUP_FINGERPRINT_CUTS answers hashlib's digests of the
    daemon's own cuts after ``sleep_s``; queries find nothing; commits
    succeed.  Counts what the daemon's connection pool does."""

    def __init__(self, path: str, sleep_s: float):
        self.sleep_s = sleep_s
        self.accepted = 0          # connections the daemon opened
        self.open = 0              # ... and still holds
        self.in_fingerprint = 0
        self.most_in_fingerprint = 0
        self._lock = threading.Lock()
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(path)
        self._listener.listen(64)
        threading.Thread(target=self._accept, daemon=True).start()

    def close(self) -> None:
        self._listener.close()

    def _accept(self) -> None:
        while True:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            with self._lock:
                self.accepted += 1
                self.open += 1
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn: socket.socket) -> None:
        try:
            while True:
                hdr = DedupSidecar._recv_exact(conn, HEADER_SIZE)
                if hdr is None:
                    return
                h = unpack_header(hdr)
                body = DedupSidecar._recv_exact(conn, h.pkg_len)
                if body is None:
                    return
                reply = b""
                if h.cmd == StorageCmd.DEDUP_FINGERPRINT_CUTS:
                    reply = self._fingerprint(body)
                conn.sendall(struct.pack(">qBB", len(reply), h.cmd, 0)
                             + reply)
        finally:
            conn.close()
            with self._lock:
                self.open -= 1

    def _fingerprint(self, body: bytes) -> bytes:
        with self._lock:
            self.in_fingerprint += 1
            self.most_in_fingerprint = max(self.most_in_fingerprint,
                                           self.in_fingerprint)
        try:
            time.sleep(self.sleep_s)
            _session, base, n = struct.unpack_from(">qqq", body)
            ends = struct.unpack_from(f">{n}q", body, 24)
            data = memoryview(body)[24 + 8 * n:]
            out, at = [struct.pack(">q", n)], 0
            for end in ends:
                out.append(struct.pack(">qq", base + at, end - at)
                           + hashlib.sha1(data[at:end]).digest())
                at = end
            return b"".join(out)
        finally:
            with self._lock:
                self.in_fingerprint -= 1


def test_six_workers_hold_six_uploads_in_the_sidecar_at_once(tmp_path):
    """Six chunked uploads sent at once to a pool pinned at six finish in
    well under the three sleeps that two workers would need, on six
    sidecar connections that the daemon then keeps idle (four was the
    constant cap: the fifth and sixth used to be closed behind every
    RPC), and every recipe reads back whole."""
    workers, sleep_s = 6, 1.0
    sock = os.path.join(str(tmp_path), "sleepy.sock")
    sidecar = SleepySidecar(sock, sleep_s)
    st = start_storage(str(tmp_path / "st"), dedup_mode="sidecar",
                       dedup_sidecar=sock,
                       extra=f"disk_writer_threads = {workers}")
    rng = random.Random(32)
    payloads = [rng.randbytes((200 << 10) + 4099 * i) for i in range(workers)]

    def upload(data: bytes) -> str:
        with StorageClient(st.ip, st.port) as sc:
            return sc.upload_buffer(data, ext="bin")

    try:
        with StorageClient(st.ip, st.port) as sc:
            sc.active_test()
        t0 = time.perf_counter()
        with ThreadPoolExecutor(workers) as pool:
            fids = list(pool.map(upload, payloads))
        took = time.perf_counter() - t0
        assert sidecar.most_in_fingerprint == workers
        assert took < 2 * sleep_s, f"six uploads took {took:.2f} s"
        assert len(recipe_keys(str(tmp_path / "st"))) == workers
        with StorageClient(st.ip, st.port) as sc:
            for fid, data in zip(fids, payloads):
                assert sc.download_to_buffer(fid) == data
        assert sidecar.accepted <= workers, sidecar.accepted
        assert sidecar.open == workers, sidecar.open
    finally:
        st.stop()
        sidecar.close()
