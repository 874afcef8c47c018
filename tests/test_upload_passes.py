"""How often the daemon's upload path passes over an uploaded byte
(``native/storage/server.cc``: the receive stage; ``ingest.cc``:
``StoreChunked``).

* The receive stage's whole-file SHA-1 runs only where ``Judge`` /
  ``Commit`` can read it: an upload that is neither an appender nor
  chunk-eligible.  A chunk-eligible upload whose chunked store failed is
  stored flat as before, and takes the digest from its tmp file then
  (``upload.fallback_rehash``); ``upload.recv_hashed_bytes`` counts the
  bytes the receive stage hashed.
* ``StoreChunked`` reads each ``dedup_segment_bytes`` segment of the
  tmp file into a buffer its thread keeps: the recipe is the reference's
  (``benchmark/reference.py``) at every segment boundary, and an upload
  never sees the bytes of the one before it on the same worker.

``Crc32``'s loops are held to zlib in ``tests/test_native_common.py`` and
to the one-table loop in ``native/tests/common_test.cc``.
"""

from __future__ import annotations

import glob
import os
import random
import socket
import struct
import sys
import threading

import pytest

from fastdfs_tpu import monitor as M
from fastdfs_tpu.client.storage_client import StorageClient
from fastdfs_tpu.common.protocol import HEADER_SIZE, StorageCmd, unpack_header
from fastdfs_tpu.sidecar import DedupSidecar
from harness import recipe_keys, start_storage

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
import reference  # noqa: E402  — benchmark/reference.py
from run import RecipeReader  # noqa: E402  — benchmark/run.py

K, M1 = 1 << 10, 1 << 20
THRESHOLD = 64 * K                # dedup_chunk_threshold as shipped
WIDTHS = (4 * K, 13, 64 * K)


def _registry(st) -> dict:
    with StorageClient(st.ip, st.port) as sc:
        return M.decode_registry(sc.stat())


def _passes(st) -> dict:
    reg = _registry(st)
    return {"hashed": reg["counters"]["upload.recv_hashed_bytes"],
            "rehash": reg["counters"]["upload.fallback_rehash"],
            "hits": reg["gauges"]["store.dedup_hits"]}


def _flat_files(base: str, fid: str) -> list[str]:
    name = os.path.basename(fid)
    return [p for p in glob.glob(os.path.join(base, "data", "**", name),
                                 recursive=True) if os.path.isfile(p)]


def _tmp_files(base: str) -> list[str]:
    return glob.glob(os.path.join(base, "tmp", "upload_*"))


# -- the receive stage ---------------------------------------------------------

class IndexOnlySidecar:
    """A sidecar whose whole-file index works and whose fingerprint path
    does not: ``commitfile`` / DEDUP_QUERY answer as the real one does,
    DEDUP_FINGERPRINT_CUTS answers status 5.  Every chunked store fails
    over it, so every chunk-eligible upload falls to the flat path with
    ``Judge`` and ``Commit`` alive behind it."""

    def __init__(self, path: str):
        self.files: dict[bytes, bytes] = {}
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(path)
        self._listener.listen(16)
        threading.Thread(target=self._accept, daemon=True).start()

    def close(self) -> None:
        self._listener.close()

    def _accept(self) -> None:
        while True:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn: socket.socket) -> None:
        with conn:
            while True:
                hdr = DedupSidecar._recv_exact(conn, HEADER_SIZE)
                if hdr is None:
                    return
                h = unpack_header(hdr)
                body = DedupSidecar._recv_exact(conn, h.pkg_len)
                if body is None:
                    return
                status, reply = 0, b""
                if h.cmd == StorageCmd.DEDUP_FINGERPRINT_CUTS:
                    status = 5
                elif h.cmd == StorageCmd.DEDUP_QUERY:
                    reply = self.files.get(bytes(body), b"")
                elif bytes(body).startswith(b"commitfile "):
                    _, digest, fid = bytes(body).split(b" ")
                    self.files.setdefault(digest, fid)
                conn.sendall(struct.pack(">qBB", len(reply), h.cmd, status)
                             + reply)


CASES = {
    # name: (dedup_mode, sidecar, size, appender,
    #        expected: stored flat, second is a hard link, hashed, rehashes)
    "eligible_fingerprints_fail": ("sidecar", "index_only", 300 * K + 7,
                                   False, True, True, 0, 2),
    "eligible_sidecar_dead": ("sidecar", "dead", 300 * K + 7,
                              False, True, False, 0, 2),
    "under_threshold": ("cpu", None, THRESHOLD - 1, False, True, True, 2, 0),
    "eligible_chunked": ("cpu", None, 300 * K + 7, False, False, False, 0, 0),
    "appender": ("cpu", None, 300 * K + 7, True, True, False, 0, 0),
}


@pytest.mark.parametrize("case", CASES)
def test_receive_stage_hashes_only_where_the_digest_is_read(tmp_path, case):
    """Two identical uploads a case.  What is stored and what the second
    upload is answered with are the parent's; the counters say which
    uploads the receive stage hashed (``hashed``, in uploads' sizes) and
    which took the digest from the tmp file after a failed chunked store
    (``rehashes``)."""
    (mode, sidecar_kind, size, appender,
     flat, linked, hashed, rehashes) = CASES[case]
    sock, sidecar = "", None
    if sidecar_kind is not None:
        sock = os.path.join(str(tmp_path), "sc.sock")
        if sidecar_kind == "index_only":
            sidecar = IndexOnlySidecar(sock)
    base = str(tmp_path / "st")
    st = start_storage(base, dedup_mode=mode, dedup_sidecar=sock)
    data = random.Random(34).randbytes(size)
    try:
        before = _passes(st)
        with StorageClient(st.ip, st.port) as sc:
            first = sc.upload_buffer(data, ext="bin", appender=appender)
            second = sc.upload_buffer(data, ext="bin", appender=appender)
            assert first != second
            for fid in (first, second):
                assert sc.download_to_buffer(fid) == data
            if appender:
                sc.append_buffer(first, b"tail")
                assert sc.download_to_buffer(first) == data + b"tail"
                assert sc.download_to_buffer(second) == data
        after = _passes(st)
        assert after["hashed"] - before["hashed"] == hashed * size
        assert after["rehash"] - before["rehash"] == rehashes
        for fid in (first, second):
            assert bool(_flat_files(base, fid)) == flat
        if not flat:
            assert len(recipe_keys(base)) == 2
        if flat and not appender:
            a = os.stat(_flat_files(base, first)[0])
            b = os.stat(_flat_files(base, second)[0])
            assert (a.st_ino == b.st_ino) == linked
            assert after["hits"] - before["hits"] == (1 if linked else 0)
        assert _tmp_files(base) == []
        reg = _registry(st)
        assert reg["gauges"]["crc32.impl"] in (0, 1)
        want = "folded" if reg["gauges"]["crc32.impl"] else "sliced"
        assert f"crc32={want}" in st.stderr_text + st.stdout_text
    finally:
        st.stop()
        if sidecar is not None:
            sidecar.close()


# -- the segment ---------------------------------------------------------------

def _widths(segment: int) -> dict:
    return {"cdc_min_size": WIDTHS[0], "cdc_avg_bits": WIDTHS[1],
            "cdc_max_size": WIDTHS[2], "dedup_chunk_threshold": THRESHOLD,
            "dedup_segment_bytes": segment}


def _one_worker(tmp_path, segment: int):
    return start_storage(
        str(tmp_path / "st"), dedup_mode="cpu",
        extra="dedup_cdc_widths = %d:%d:%d\n" % WIDTHS
        + f"dedup_segment_bytes = {segment}\ndisk_writer_threads = 1")


def _stored_as_the_reference_cuts(st, data: bytes, segment: int) -> str:
    with StorageClient(st.ip, st.port) as sc:
        fid = sc.upload_buffer(data, ext="bin")
        assert sc.download_to_buffer(fid) == data
    reader = RecipeReader(st.port)
    try:
        got, logical = reader.fetch(fid)
    finally:
        reader.close()
    assert logical == len(data)
    assert got == reference.recipe(data, _widths(segment))
    return fid


# 1 MiB is the least dedup_segment_bytes the daemon takes; the second
# segment size is no multiple of a page, so every segment but the first
# starts inside one.
@pytest.mark.parametrize("segment", [M1, M1 + 4099])
@pytest.mark.parametrize("size_of", [
    lambda seg: THRESHOLD + 1, lambda seg: seg, lambda seg: seg + 1,
    lambda seg: 3 * seg + seg // 2 + 13],
    ids=["threshold_plus_1", "one_segment", "one_segment_plus_1",
         "three_and_a_half_segments"])
def test_segments_of_the_tmp_file_give_the_reference_recipe(
        tmp_path, segment, size_of):
    size = size_of(segment)
    data = random.Random(size).randbytes(size)
    st = _one_worker(tmp_path, segment)
    try:
        _stored_as_the_reference_cuts(st, data, segment)
        assert _tmp_files(str(tmp_path / "st")) == []
    finally:
        st.stop()


def test_uploads_in_a_row_on_one_worker_do_not_see_each_other(tmp_path):
    """One dio worker, so one kept segment buffer, lent back to the kernel
    after each upload (MADV_FREE): a long upload, then a short one whose
    only segment ends inside what the long one left in the buffer, then
    one longer than anything before it."""
    rng = random.Random(3434)
    st = _one_worker(tmp_path, M1)
    try:
        for size in (2 * M1 + 4097, 200 * K + 3, M1 - 5, 3 * M1 + 777):
            _stored_as_the_reference_cuts(st, rng.randbytes(size), M1)
        assert _tmp_files(str(tmp_path / "st")) == []
        assert _registry(st)["gauges"]["dio.workers"] == 1
    finally:
        st.stop()
