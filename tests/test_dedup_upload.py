"""Dedup-aware negotiated uploads (UPLOAD_RECIPE / UPLOAD_CHUNKS).

Layers:
- pure-Python: the NumPy CDC twin is cut-identical to the serial
  reference, the client fingerprint pipeline covers the stream, the wire
  encoders round-trip, and gen_protocol refuses opcode collisions;
- cross-language golden: ``fdfs_codec ingest-wire`` emits the canonical
  phase-1/phase-2 byte layouts, which must equal the Python client's
  encoders hex-for-hex;
- integration: a live 1-tracker/2-storage group — a warm re-upload via
  the negotiated path ships ZERO data bytes, the returned ID downloads
  byte-identical, the file replicates and disk-recovers, fallbacks are
  transparent, and an abandoned session releases its chunk pins on
  timeout (no pin leak).  The concurrency test doubles as the TSan
  target wired into tools/run_sanitizers.sh.
"""

import hashlib
import os
import shutil
import socket
import struct
import subprocess
import threading
import time
import zlib

import numpy as np
import pytest

from fastdfs_tpu.client import FdfsClient, StorageClient, TrackerClient
from fastdfs_tpu.client.conn import Connection, ProtocolError, StatusError
from fastdfs_tpu.client.fingerprint import (SHIPPED_PARAMS,
                                            fingerprint_buffer)
from fastdfs_tpu.client.storage_client import (
    pack_upload_chunks_prefix,
    pack_upload_recipe,
    unpack_upload_recipe_resp,
)
from fastdfs_tpu.common.protocol import (
    HEADER_SIZE,
    StorageCmd,
    pack_chunking,
    pack_header,
    unpack_header,
)
from fastdfs_tpu.ops import gear_cdc
from tests.harness import (BUILD, Daemon, STORAGED, TRACKERD, free_port,
                           start_storage, start_tracker, upload_retry)

_HAVE_TOOLCHAIN = ((shutil.which("cmake") is not None
                    and shutil.which("ninja") is not None)
                   or shutil.which("g++") is not None)
_HAVE_BINARIES = os.path.exists(STORAGED) and os.path.exists(TRACKERD)
needs_native = pytest.mark.skipif(
    not (_HAVE_TOOLCHAIN or _HAVE_BINARIES),
    reason="no native toolchain and no prebuilt daemons")

HB = "heart_beat_interval = 1\nstat_report_interval = 1"


def _wait(cond, timeout=30, interval=0.3):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(interval)
    return None


# ---------------------------------------------------------------------------
# client-side fingerprinting
# ---------------------------------------------------------------------------

def test_numpy_cdc_matches_serial_reference():
    rng = np.random.default_rng(11)
    for n in (1, 31, 32, 2048, 100_000):
        data = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        assert (gear_cdc.chunk_stream_np(data)
                == gear_cdc.chunk_stream_ref(data)), n
    # low-entropy stream: only max_size cuts fire
    data = b"\x00" * 150_000
    assert gear_cdc.chunk_stream_np(data) == gear_cdc.chunk_stream_ref(data)
    assert gear_cdc.chunk_stream_np(b"") == []


def test_fingerprint_buffer_covers_stream_with_true_digests():
    rng = np.random.default_rng(12)
    data = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()
    fps = fingerprint_buffer(data, SHIPPED_PARAMS)
    assert sum(fp.length for fp in fps) == len(data)
    cuts = gear_cdc.chunk_stream_ref(data)
    assert [fp.length for fp in fps] == [
        e - s for s, e in zip([0] + cuts[:-1], cuts)]
    start = 0
    for fp in fps:
        assert fp.digest == hashlib.sha1(data[start:start + fp.length]).digest()
        start += fp.length
    assert fingerprint_buffer(b"", SHIPPED_PARAMS) == []


# ---------------------------------------------------------------------------
# wire encoding + opcode hygiene
# ---------------------------------------------------------------------------

def test_upload_recipe_wire_roundtrip():
    chunks = [(100, b"\x01" * 20), (200, b"\x02" * 20)]
    body = pack_upload_recipe(0xFF, "bin", 0xDEADBEEF, 300, chunks)
    assert body[0] == 0xFF
    assert body[1:7] == b"bin\x00\x00\x00"
    assert struct.unpack(">q", body[7:15])[0] == 0xDEADBEEF
    assert struct.unpack(">q", body[15:23])[0] == 300
    assert struct.unpack(">q", body[23:31])[0] == 2
    assert len(body) == 31 + 2 * 28
    with pytest.raises(ValueError):
        pack_upload_recipe(0, "", 0, 1, [(1, b"short")])
    session, bitmap = unpack_upload_recipe_resp(
        struct.pack(">q", 42) + b"\x00\x01", 2)
    assert session == 42 and bitmap == b"\x00\x01"
    with pytest.raises(ProtocolError):
        unpack_upload_recipe_resp(b"\x00" * 9, 2)
    assert pack_upload_chunks_prefix(7, 1000) == struct.pack(">qq", 7, 1000)


def test_gen_protocol_rejects_opcode_collisions():
    import enum
    import importlib
    import sys

    native_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")
    if native_dir not in sys.path:
        sys.path.insert(0, native_dir)
    gen_protocol = importlib.import_module("gen_protocol")

    # Python's Enum silently turns a duplicate value into an ALIAS (the
    # silent failure mode the validation exists for); the check now
    # lives at the MANIFEST layer, where every enumerator is plain data.
    class Collides(enum.IntEnum):
        A = 7
        B = 7
        C = 9

    manifest = gen_protocol.build_manifest()
    manifest["enums"]["Collides"] = [
        {"name": n, "cpp": gen_protocol._cpp_name(n), "value": int(m.value)}
        for n, m in Collides.__members__.items()]
    with pytest.raises(SystemExit, match="duplicate opcode.*A/B = 7"):
        gen_protocol.validate_manifest(manifest)
    # the real manifest must pass (and stay collision-free)
    gen_protocol.validate_manifest(gen_protocol.build_manifest())


# ---------------------------------------------------------------------------
# streaming request bodies (conn iterable-body support)
# ---------------------------------------------------------------------------

def test_iterable_body_requires_length_and_checks_it():
    class _FakeConn(Connection):
        def __init__(self):  # no real socket
            self.host, self.port = "x", 0
            self.timeout = 1
            self.broken = False
            self.trace_ctx = None
            self.priority = None
            self.sent = bytearray()
            self.sock = self

        def sendall(self, b):
            self.sent += b

    c = _FakeConn()
    with pytest.raises(ValueError):
        c.send_request(11, iter([b"abc"]))
    # declared 6, produced 3: framing would desync — broken + raised
    with pytest.raises(ProtocolError):
        c.send_request(11, iter([b"abc"]), body_len=6)
    assert c.broken
    c.broken = False
    c.sent.clear()
    c.send_request(11, iter([b"abc", b"", b"def"]), body_len=6)
    hdr = unpack_header(bytes(c.sent[:HEADER_SIZE]))
    assert hdr.pkg_len == 6 and hdr.cmd == 11
    assert bytes(c.sent[HEADER_SIZE:]) == b"abcdef"
    assert not c.broken


# ---------------------------------------------------------------------------
# cross-language golden: codec layout == python client layout
# ---------------------------------------------------------------------------

@needs_native
def test_ingest_wire_golden():
    codec = os.path.join(BUILD, "fdfs_codec")
    out = subprocess.run([codec, "ingest-wire"], capture_output=True,
                         check=True).stdout.decode()
    got = dict(line.split("=", 1) for line in out.splitlines() if "=" in line)
    chunks = [(1000, hashlib.sha1(b"a" * 1000).digest()),
              (2000, hashlib.sha1(b"b" * 2000).digest()),
              (3000, hashlib.sha1(b"c" * 3000).digest())]
    assert got["request"] == pack_upload_recipe(
        3, "bin", 0x11223344, 6000, chunks).hex()
    session, bitmap = unpack_upload_recipe_resp(
        bytes.fromhex(got["response"]), 3)
    assert session == 0x0102030405060708
    assert bitmap == b"\x01\x00\x01"
    assert got["chunks_prefix"] == pack_upload_chunks_prefix(
        0x0102030405060708, 4000).hex()
    # QUERY_CHUNKING's answer: how a node at 512K:20:8M cuts
    assert got["chunking"] == pack_chunking(dict(
        min_size=512 << 10, avg_bits=20, max_size=8 << 20, cdc_policy=1,
        chunk_threshold=65536, segment_bytes=64 << 20)).hex()


# ---------------------------------------------------------------------------
# integration
# ---------------------------------------------------------------------------

S1_IP, S2_IP = "127.0.0.41", "127.0.0.42"


def _ingest_counters(ip, port):
    with StorageClient(ip, port) as sc:
        reg = sc.stat()
    return ({k: v for k, v in reg["counters"].items()
             if k.startswith("ingest.")},
            reg["gauges"].get("ingest.sessions_active", -1))


@needs_native
def test_negotiated_upload_live_cluster(tmp_path_factory):
    """The acceptance path: warm re-upload ships zero data chunks, wire
    savings > 0.9x payload, the ID downloads byte-identical, the file
    replicates, and a wiped replica disk-recovers it."""
    tracker = start_tracker(tmp_path_factory.mktemp("tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    s1 = start_storage(tmp_path_factory.mktemp("s1"), trackers=[taddr],
                       dedup_mode="cpu", extra=HB, ip=S1_IP)
    s2dir = tmp_path_factory.mktemp("s2")
    s2_port = free_port()
    s2 = start_storage(s2dir, port=s2_port, trackers=[taddr],
                       dedup_mode="cpu", extra=HB, ip=S2_IP)
    t = TrackerClient("127.0.0.1", tracker.port)
    cli = FdfsClient([taddr])
    payload = os.urandom(256 * 1024)
    try:
        assert _wait(lambda: t.list_groups()
                     and t.list_groups()[0]["active"] == 2)
        upload_retry(cli, b"warmup " * 64, ext="bin")

        s_first, s_second = {}, {}
        fid1 = cli.upload_buffer_dedup(payload, ext="bin",
                                       min_dup_ratio=0, stats=s_first)
        # Wait until fid1 replicated: chunk-aware sync populates the
        # PEER's chunk store too, so the warm re-upload is all-present
        # regardless of which member round-robin picks.
        assert _wait(lambda: len(t.query_fetch_all(fid1)) == 2), \
            "first negotiated upload never replicated"
        fid2 = cli.upload_buffer_dedup(payload, ext="bin",
                                       min_dup_ratio=0, stats=s_second)
        # Both took the negotiated path; the second shipped NOTHING.
        assert s_first["fallback"] == "" and s_second["fallback"] == ""
        assert s_second["chunks_missing"] == 0
        assert s_second["bytes_sent"] == 0
        assert cli.download_to_buffer(fid1) == payload
        assert cli.download_to_buffer(fid2) == payload

        # Wire accounting on whichever storage served the uploads.
        def saved():
            total = 0
            for ip in (S1_IP, S2_IP):
                c, _ = _ingest_counters(ip, s1.port if ip == S1_IP
                                        else s2.port)
                total += c.get("ingest.bytes_saved_wire", 0)
            return total
        assert saved() >= 0.9 * len(payload), saved()

        # Server-authoritative threshold: a payload below the daemon's
        # dedup_chunk_threshold (64K default) answers ENOTSUP even when
        # the client skips its own size gate — transparent fallback.
        small_stats: dict = {}
        small = os.urandom(16 * 1024)
        with StorageClient(S1_IP, s1.port) as sc:
            fid_small = sc.upload_buffer_dedup(small, ext="bin",
                                               stats=small_stats)
        assert small_stats["fallback"] == "status95"
        with StorageClient(S1_IP, s1.port) as sc:
            assert sc.download_to_buffer(fid_small) == small

        # Replicates: both members eventually serve fid2.
        assert _wait(lambda: len(t.query_fetch_all(fid2)) == 2), \
            "negotiated upload never replicated"
        for ip in (S1_IP, S2_IP):
            with StorageClient(ip, s1.port if ip == S1_IP
                               else s2_port) as sc:
                assert sc.download_to_buffer(fid2) == payload

        # Recovers: wipe s2's data (keep sync state) and restart — the
        # rebuilt node must serve the negotiated upload byte-identical.
        s2.stop()
        data_dir = os.path.join(str(s2dir), "data")
        for name in os.listdir(data_dir):
            if name == "sync":
                continue
            p = os.path.join(data_dir, name)
            shutil.rmtree(p) if os.path.isdir(p) else os.unlink(p)
        s2 = Daemon(STORAGED, os.path.join(str(s2dir), "storage.conf"),
                    s2_port, ip=S2_IP)
        assert _wait(lambda: _recovered(S2_IP, s2_port, fid2, payload),
                     timeout=60), "recovered node never served the file"
    finally:
        s2.stop()
        s1.stop()
        tracker.stop()


def _recovered(ip, port, fid, payload):
    try:
        with StorageClient(ip, port) as sc:
            return sc.download_to_buffer(fid) == payload
    except (OSError, ProtocolError, StatusError):
        return False


@needs_native
def test_negotiated_upload_falls_back_without_chunk_store(tmp_path_factory):
    """A daemon that cannot serve the opcodes (dedup off => ENOTSUP; an
    older daemon answers EINVAL the same way) must not break uploads:
    the client transparently re-sends via plain UPLOAD_FILE."""
    tracker = start_tracker(tmp_path_factory.mktemp("tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    storage = start_storage(tmp_path_factory.mktemp("st"), trackers=[taddr],
                            dedup_mode="none", extra=HB)
    cli = FdfsClient([taddr], dedup_uploads=True, dedup_min_ratio=0.0)
    payload = os.urandom(128 * 1024)
    try:
        upload_retry(cli, b"warmup " * 64, ext="bin")
        stats = {}
        fid = cli.upload_buffer_dedup(payload, ext="bin", min_dup_ratio=0,
                                      stats=stats)
        # the node does not say how it cuts: nothing to cut with
        assert stats["fallback"] == "no_chunking_params"
        assert cli.download_to_buffer(fid) == payload
        # the opt-in flag routes upload_buffer through the same path
        fid2 = cli.upload_buffer(payload, ext="bin")
        assert cli.download_to_buffer(fid2) == payload
        c, _ = _ingest_counters("127.0.0.1", storage.port)
        assert c.get("ingest.recipe_fallbacks", 0) >= 1
    finally:
        storage.stop()
        tracker.stop()


@needs_native
def test_tail_edited_reupload_ships_only_the_changed_chunks(tmp_path_factory):
    """Cold, warm and tail-edited passes over one blob: the cold pass
    ships it all, the warm pass nothing, and a blob whose last eighth was
    rewritten ships strictly between the two: the head's chunks are
    found, the tail's are sent."""
    tracker = start_tracker(tmp_path_factory.mktemp("tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    storage = start_storage(tmp_path_factory.mktemp("st"), trackers=[taddr],
                            dedup_mode="cpu", extra=HB)
    cli = FdfsClient([taddr])
    blob = os.urandom(256 * 1024)
    edited = blob[:-len(blob) // 8] + os.urandom(len(blob) // 8)
    try:
        upload_retry(cli, b"warmup " * 64, ext="bin")
        sent = {}
        for name, data in (("cold", blob), ("warm", blob), ("edited", edited)):
            stats = {}
            fid = cli.upload_buffer_dedup(data, ext="bin", min_dup_ratio=0,
                                          stats=stats)
            assert stats["fallback"] == "", (name, stats)
            assert cli.download_to_buffer(fid) == data
            sent[name] = stats["bytes_sent"]
        assert sent["cold"] >= len(blob)
        assert sent["warm"] == 0
        assert len(blob) // 8 <= sent["edited"] < len(blob) // 2, sent
        c, _ = _ingest_counters("127.0.0.1", storage.port)
        assert c["ingest.recipe_uploads"] == 3
        assert c["ingest.bytes_saved_wire"] >= len(blob) + len(blob) // 2
    finally:
        cli.close()
        storage.stop()
        tracker.stop()


@needs_native
def test_upload_session_timeout_releases_pins(tmp_path_factory):
    """A client that sends UPLOAD_RECIPE and vanishes must not leak pins:
    chunks it held present survive a concurrent delete only until the
    session sweep fires, then their deferred unlink completes."""
    tracker = start_tracker(tmp_path_factory.mktemp("tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    stdir = tmp_path_factory.mktemp("st")
    storage = start_storage(
        stdir, trackers=[taddr], dedup_mode="cpu",
        extra=HB + "\nupload_session_timeout = 1")
    cli = FdfsClient([taddr])
    payload = os.urandom(128 * 1024)
    try:
        upload_retry(cli, b"warmup " * 64, ext="bin")
        fid = cli.upload_buffer_dedup(payload, ext="bin", min_dup_ratio=0)
        from harness import chunk_digests
        n_chunks = len(chunk_digests(str(stdir)))
        assert n_chunks > 0

        # Phase 1 on a raw socket, then "vanish" (no phase 2).
        chunks = [(fp.length, fp.digest)
                  for fp in fingerprint_buffer(payload, SHIPPED_PARAMS)]
        body = pack_upload_recipe(0xFF, "bin", zlib.crc32(payload),
                                  len(payload), chunks)
        sock = socket.create_connection(("127.0.0.1", storage.port),
                                        timeout=10)
        sock.sendall(pack_header(len(body), StorageCmd.UPLOAD_RECIPE) + body)
        resp_hdr = unpack_header(_recv_exact(sock, HEADER_SIZE))
        resp = _recv_exact(sock, resp_hdr.pkg_len)
        assert resp_hdr.status == 0
        _, bitmap = unpack_upload_recipe_resp(resp, len(chunks))
        assert bitmap == b"\x00" * len(chunks)  # everything present
        _, active = _ingest_counters("127.0.0.1", storage.port)
        assert active == 1

        # Delete the only file referencing those chunks: refs drop to 0
        # but the session's pins defer every unlink.
        cli.delete_file(fid)
        still = len(chunk_digests(str(stdir)))
        assert still == n_chunks, "pinned chunks were unlinked by delete"

        sock.close()  # the vanished client
        # timeout=1s + 2s sweep granularity: pins released, unlinks done.
        assert _wait(lambda: _ingest_counters(
            "127.0.0.1", storage.port)[1] == 0, timeout=10)
        assert _wait(lambda: len(chunk_digests(str(stdir))) == 0,
                     timeout=10), \
            "deferred unlinks never completed after session expiry"
        c, _ = _ingest_counters("127.0.0.1", storage.port)
        assert c.get("ingest.recipe_fallbacks", 0) >= 1  # the expiry
    finally:
        storage.stop()
        tracker.stop()


def _recv_exact(sock, n):
    buf = b""
    while len(buf) < n:
        got = sock.recv(n - len(buf))
        if not got:
            raise ConnectionError("peer closed")
        buf += got
    return buf


@needs_native
def test_concurrent_negotiated_uploads_and_deletes(tmp_path_factory):
    """Pin/ref discipline under concurrency (the TSan target wired into
    tools/run_sanitizers.sh): negotiated uploads sharing chunk content
    race deletes of earlier files; every surviving file must download
    byte-identical and no session may leak."""
    tracker = start_tracker(tmp_path_factory.mktemp("tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    storage = start_storage(tmp_path_factory.mktemp("st"), trackers=[taddr],
                            dedup_mode="cpu", extra=HB)
    shared = os.urandom(160 * 1024)
    errors: list[str] = []
    try:
        warm = FdfsClient([taddr])
        upload_retry(warm, b"warmup " * 64, ext="bin")

        def worker(i):
            try:
                cli = FdfsClient([taddr])
                kept = []
                for j in range(4):
                    # shared head (dedup hits across workers) + unique tail
                    data = shared + os.urandom(4096 * (i + 1) + j)
                    fid = cli.upload_buffer_dedup(data, ext="bin",
                                                  min_dup_ratio=0)
                    kept.append((fid, data))
                    if j % 2 == 1:
                        vic, _ = kept.pop(0)
                        cli.delete_file(vic)
                for fid, data in kept:
                    if cli.download_to_buffer(fid) != data:
                        errors.append(f"worker {i}: {fid} corrupt")
                cli.close()
            except Exception as e:  # surface, don't hang the join
                errors.append(f"worker {i}: {e!r}")

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not errors, errors
        assert _wait(lambda: _ingest_counters(
            "127.0.0.1", storage.port)[1] == 0, timeout=10), \
            "sessions leaked after concurrent run"
    finally:
        storage.stop()
        tracker.stop()


@needs_native
def test_negotiated_upload_sidecar_reindexes_near_dups(tmp_path):
    """Sidecar mode keeps the near-dup index outside the chunk store and
    the client-side fingerprint pipeline never talks to it: a negotiated
    upload must still be fed through the plugin (the recovery-reindex
    path), or NEAR_DUPS would be blind to every dedup-uploaded file."""
    import sys as _sys

    _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_chunked_storage import _start_sidecar

    sc_proc, sock = _start_sidecar(tmp_path)
    tracker = start_tracker(os.path.join(str(tmp_path), "tr"))
    taddr = f"127.0.0.1:{tracker.port}"
    storage = start_storage(os.path.join(str(tmp_path), "st"),
                            trackers=[taddr], dedup_mode="sidecar",
                            dedup_sidecar=sock, extra=HB)
    cli = FdfsClient([taddr])
    rng = np.random.default_rng(33)
    base = rng.integers(0, 256, size=1 << 20, dtype=np.uint8).tobytes()
    variant = base[: (1 << 20) - 4096] + os.urandom(4096)
    try:
        upload_retry(cli, b"warmup " * 64, ext="bin")
        fid_a = cli.upload_buffer(base, ext="bin")  # plain path: indexed
        stats: dict = {}
        fid_b = cli.upload_buffer_dedup(variant, ext="bin",
                                        min_dup_ratio=0, stats=stats)
        assert stats["fallback"] == ""
        assert stats["chunks_missing"] < stats["chunks_total"]  # dedup hit
        # The negotiated upload carries a signature (was reindexed) and
        # its near-dups resolve to the plain-uploaded neighbour.
        near = _wait(lambda: [p for p in cli.near_dups(fid_b)
                              if p[0] == fid_a], timeout=20)
        assert near, f"negotiated upload invisible to NEAR_DUPS: " \
                     f"{cli.near_dups(fid_b)}"
        assert cli.download_to_buffer(fid_b) == variant
    finally:
        cli.close()
        storage.stop()
        tracker.stop()
        sc_proc.kill()
        sc_proc.wait()


@needs_native
def test_upload_file_streams_in_segments(tmp_path, tmp_path_factory):
    """upload_file must hold O(segment) memory: the body goes out through
    the iterable-body path in bounded reads, and the result is
    byte-identical to a buffer upload."""
    storage = start_storage(tmp_path_factory.mktemp("st"))
    path = os.path.join(str(tmp_path), "big.bin")
    data = os.urandom(3 * (1 << 20) + 12345)
    with open(path, "wb") as fh:
        fh.write(data)
    reads = []
    real_read = open(path, "rb").read  # noqa: F841  (sentinel only)

    class CountingFile:
        def __init__(self, p):
            self._fh = open(p, "rb")

        def read(self, n):
            reads.append(n)
            return self._fh.read(n)

        def close(self):
            self._fh.close()

    try:
        with StorageClient("127.0.0.1", storage.port) as sc:
            fh = CountingFile(path)
            fid = sc.upload_stream(fh, len(data), ext="bin",
                                   segment=256 * 1024)
            fh.close()
        assert max(reads) <= 256 * 1024  # never slurps
        assert len(reads) >= len(data) // (256 * 1024)
        with StorageClient("127.0.0.1", storage.port) as sc:
            assert sc.download_to_buffer(fid) == data
        # and the path-based API streams too
        with StorageClient("127.0.0.1", storage.port) as sc:
            fid2 = sc.upload_file(path)
            assert sc.download_to_buffer(fid2) == data
    finally:
        storage.stop()
