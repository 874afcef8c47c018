"""Integrity engine: background scrub, bit-rot quarantine + replica
repair, and zero-ref chunk GC (ISSUE 4).

Layers:
- pure-Python contract tests (SCRUB_STATUS blob naming/codec);
- a cross-language golden: the C++ blob (fdfs_codec scrub-status) must
  decode field-for-field in Python — pinning slot order AND count;
- the sidecar's DEDUP_VERIFY batch-hash handler (device path with a
  hashlib referee);
- live clusters: the full corruption lifecycle (inject bit-rot ->
  scrub detects -> quarantine -> repair from the replica -> download is
  byte-identical), the single-replica unrepairable case, zero-ref GC
  after DELETE_FILE, the recipe-sidecar delete satellite, a
  scrub-vs-traffic race (the TSan target in tools/run_sanitizers.sh),
  and the scrubber's own timer, paced and unpaced, under foreground IO.
"""

import os
import shutil
import subprocess
import sys
import threading
import time

import pytest

from fastdfs_tpu.common import protocol as P
from tests.harness import (BUILD, REPO, STORAGED, TRACKERD,
                           chunk_digests, corrupt_chunk, free_port,
                           read_chunk_payload, recipe_keys,
                           slab_records, start_storage, start_tracker,
                           upload_retry)

_HAVE_TOOLCHAIN = ((shutil.which("cmake") is not None
                    and shutil.which("ninja") is not None)
                   or shutil.which("g++") is not None)
_HAVE_BINARIES = os.path.exists(STORAGED) and os.path.exists(TRACKERD)
needs_native = pytest.mark.skipif(
    not (_HAVE_TOOLCHAIN or _HAVE_BINARIES),
    reason="no native toolchain and no prebuilt daemons")

HB = "heart_beat_interval = 1\nstat_report_interval = 1"
# Scrub config for tests: no periodic passes (kicks drive everything
# deterministically), 1s GC grace so delete->GC is observable fast.
SCRUB = HB + "\nscrub_interval_s = 0\nchunk_gc_grace_s = 1"


def _wait(cond, timeout=30, interval=0.25):
    deadline = time.time() + timeout
    while time.time() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(interval)
    return cond()


# ---------------------------------------------------------------------------
# wire contract
# ---------------------------------------------------------------------------

def test_scrub_stat_fields_shape():
    assert P.SCRUB_STAT_COUNT == len(P.SCRUB_STAT_FIELDS) == 18
    assert len(set(P.SCRUB_STAT_FIELDS)) == P.SCRUB_STAT_COUNT
    # The issue's headline stats are first-class named fields.
    for required in ("chunks_repaired", "corrupt_unrepairable",
                     "bytes_reclaimed", "chunks_reclaimed", "quarantined"):
        assert required in P.SCRUB_STAT_FIELDS
    assert P.StorageCmd.SCRUB_STATUS == 134
    assert P.StorageCmd.SCRUB_KICK == 135
    assert P.StorageCmd.DEDUP_VERIFY == 136


def test_scrub_stats_pack_unpack_roundtrip():
    vals = {name: i * 3 + 1 for i, name in enumerate(P.SCRUB_STAT_FIELDS)}
    blob = P.pack_scrub_stats(vals)
    assert len(blob) == 8 * P.SCRUB_STAT_COUNT
    assert P.unpack_scrub_stats(blob) == vals
    # Append-only: a shorter (older daemon) blob reads missing slots 0,
    # a longer (newer daemon) blob's extra tail is ignored.
    short = P.unpack_scrub_stats(blob[:16])
    assert short["running"] == vals["running"]
    assert short["passes"] == vals["passes"]
    assert short["bytes_reclaimed"] == 0
    extended = P.unpack_scrub_stats(blob + P.long2buff(999))
    assert extended == vals


@needs_native
def test_scrub_status_cross_language_golden():
    codec = os.path.join(BUILD, "fdfs_codec")
    out = subprocess.run([codec, "scrub-status"], capture_output=True,
                         check=True).stdout.decode()
    lines = dict(line.split("=", 1) for line in out.splitlines() if line)
    blob = bytes.fromhex(lines.pop("blob"))
    # The C++ emitter walked kScrubStatNames; the names and their order
    # must be the Python tuple, and the wire blob must decode to the
    # same fixture values.
    assert list(lines) == list(P.SCRUB_STAT_FIELDS)
    expect = {name: 1000 + 13 * i
              for i, name in enumerate(P.SCRUB_STAT_FIELDS)}
    assert {k: int(v) for k, v in lines.items()} == expect
    assert P.unpack_scrub_stats(blob) == expect


# ---------------------------------------------------------------------------
# sidecar DEDUP_VERIFY (batched accelerator hash vs hashlib referee)
# ---------------------------------------------------------------------------

def test_sidecar_verify_batch_masks_mismatches(tmp_path):
    import hashlib

    from fastdfs_tpu.sidecar import DedupSidecar

    sc = DedupSidecar(os.path.join(str(tmp_path), "unused.sock"))
    chunks = [os.urandom(n) for n in (1, 64, 1000, 4096, 70000)]
    digests = [hashlib.sha1(c).digest() for c in chunks]
    digests[2] = bytes(20)  # claim a wrong digest for chunk 2
    body = P.long2buff(len(chunks))
    for c, d in zip(chunks, digests):
        body += P.long2buff(len(c)) + d
    body += b"".join(chunks)
    status, mask = sc._verify(body)
    assert status == 0
    assert mask == bytes([0, 0, 1, 0, 0])
    # malformed bodies are refused, not crashed on
    assert sc._verify(b"\x00" * 4)[0] == 22
    assert sc._verify(P.long2buff(2) + P.long2buff(10) + bytes(20))[0] == 22


# ---------------------------------------------------------------------------
# live clusters
# ---------------------------------------------------------------------------

def _two_storage_cluster(tmp, extra):
    from fastdfs_tpu.client import FdfsClient

    tr = start_tracker(os.path.join(tmp, "tr"))
    taddr = f"127.0.0.1:{tr.port}"
    sts = []
    for i in range(2):
        # Two group members need distinct IPs (file IDs identify the
        # source by IP alone).
        ip = f"127.0.0.{60 + i}"
        sts.append(start_storage(os.path.join(tmp, f"st{i}"),
                                 port=free_port(), ip=ip, trackers=[taddr],
                                 dedup_mode="cpu", extra=extra))
    return tr, sts, FdfsClient([taddr])


@needs_native
def test_corruption_lifecycle_and_gc_two_storages(tmp_path):
    """The acceptance path: injected on-disk bit-rot is detected by a
    scrub pass, quarantined, repaired from the group replica, and a
    subsequent download returns byte-identical content; after
    DELETE_FILE drops the last ref a GC pass reclaims the chunks, and
    cli.py scrub / the stats registry report the reclaimed bytes."""
    from fastdfs_tpu.client import StorageClient

    tmp = str(tmp_path)
    tr, sts, cli = _two_storage_cluster(tmp, SCRUB)
    bases = [os.path.join(tmp, f"st{i}") for i in range(2)]
    try:
        data = os.urandom(1 << 20)  # well over dedup_chunk_threshold
        fid = upload_retry(cli, data, ext="bin")
        # Replication done: the replica holds chunk files too.
        assert _wait(lambda: all(chunk_digests(b) for b in bases),
                     timeout=40)
        # Both members hold every chunk after replication; rot node 0.
        victim = 0
        dig, path = corrupt_chunk(bases[victim])
        ip, port = sts[victim].ip, sts[victim].port

        cli.scrub_kick(ip, port)
        st = _wait(lambda: (lambda s: s if s["chunks_repaired"] >= 1
                            else None)(cli.scrub_status(ip, port)),
                   timeout=40)
        assert st, f"scrub never repaired: {cli.scrub_status(ip, port)}"
        assert st["chunks_corrupt"] >= 1
        assert st["chunks_verified"] >= 1
        assert st["bytes_verified"] > 0
        assert st["quarantined"] == 0  # repair clears the quarantine
        # The repaired chunk payload (flat file or slab record) is back
        # with the right content hash.
        import hashlib
        assert hashlib.sha1(
            read_chunk_payload(bases[victim], dig)).hexdigest() == dig
        # Byte-identical download straight from the scrubbed node.
        with StorageClient(ip, port) as sc:
            assert sc.download_to_buffer(fid) == data

        # Tracing: the pass and the repair left spans in the ring.
        with StorageClient(ip, port) as sc:
            spans = sc.trace_dump()["spans"]
        names = {s["name"] for s in spans}
        assert "scrub.pass" in names and "scrub.repair" in names

        # -- zero-ref GC after DELETE_FILE ------------------------------
        cli.delete_file(fid)
        # refs dropped -> chunks parked for GC (grace 1s), recipe gone
        st = _wait(lambda: (lambda s: s if s["gc_pending_chunks"] >= 1
                            else None)(cli.scrub_status(ip, port)))
        assert st, cli.scrub_status(ip, port)
        assert st["recipes_reclaimed"] >= 1  # .rcp deleted with the file
        time.sleep(1.2)  # let the grace window lapse
        cli.scrub_kick(ip, port)
        st = _wait(lambda: (lambda s: s if s["chunks_reclaimed"] >= 1
                            else None)(cli.scrub_status(ip, port)))
        assert st, cli.scrub_status(ip, port)
        assert st["bytes_reclaimed"] > 0
        assert _wait(lambda: not chunk_digests(bases[victim]))

        # The registry mirrors the scrub stats (fdfs_monitor surface)...
        with StorageClient(ip, port) as sc:
            gauges = sc.stat()["gauges"]
        assert gauges["scrub.chunks_repaired"] >= 1
        assert gauges["scrub.bytes_reclaimed"] == st["bytes_reclaimed"]
        # ...and the operator CLI renders the reclaimed bytes.
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-m", "fastdfs_tpu.cli", "scrub",
             f"127.0.0.1:{tr.port}"],
            capture_output=True, cwd=REPO, env=env, timeout=60)
        assert out.returncode == 0, out.stderr.decode()
        text = out.stdout.decode()
        assert "repaired: " in text and "reclaimed" in text
        assert f"({st['bytes_reclaimed']} bytes)" in text
    finally:
        for st_ in sts:
            st_.stop()
        tr.stop()


@needs_native
def test_single_replica_corruption_is_unrepairable_not_hung(tmp_path):
    """With no replica to pull from, a corrupt chunk surfaces as
    scrub.corrupt_unrepairable (and downloads fail loudly) instead of
    the scrubber hanging or serving rotted bytes."""
    from fastdfs_tpu.client import FdfsClient, StorageClient

    tmp = str(tmp_path)
    tr = start_tracker(os.path.join(tmp, "tr"))
    st = start_storage(os.path.join(tmp, "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu", extra=SCRUB)
    cli = FdfsClient([f"127.0.0.1:{tr.port}"])
    base = os.path.join(tmp, "st")
    try:
        data = os.urandom(256 << 10)
        fid = upload_retry(cli, data, ext="bin")
        assert chunk_digests(base)
        corrupt_chunk(base)
        cli.scrub_kick("127.0.0.1", st.port)
        status = _wait(
            lambda: (lambda s: s if s["corrupt_unrepairable"] >= 1
                     else None)(cli.scrub_status("127.0.0.1", st.port)),
            timeout=40)
        assert status, cli.scrub_status("127.0.0.1", st.port)
        assert status["quarantined"] >= 1
        # The bad bytes are never served: the download errors instead of
        # returning a silently-corrupt payload.
        with pytest.raises(Exception):
            with StorageClient("127.0.0.1", st.port) as sc:
                sc.download_to_buffer(fid)
        # Heal-on-upload: re-shipping the same content through the
        # negotiated path restores the quarantined chunk...
        cli.upload_buffer_dedup(data, ext="bin", min_dup_ratio=0)
        status = _wait(
            lambda: (lambda s: s if s["quarantined"] == 0 else None)(
                cli.scrub_status("127.0.0.1", st.port)))
        assert status, cli.scrub_status("127.0.0.1", st.port)
        # ...and the original file serves byte-identical again.
        assert cli.download_to_buffer(fid) == data
    finally:
        st.stop()
        tr.stop()


@needs_native
def test_delete_removes_recipe_sidecar_and_counts_bytes(tmp_path):
    """ISSUE 4 satellite: DELETE_FILE on a recipe-backed file must
    delete the .rcp sidecar with the file ID and account its bytes to
    scrub.bytes_reclaimed."""
    import glob

    from fastdfs_tpu.client import FdfsClient

    tmp = str(tmp_path)
    tr = start_tracker(os.path.join(tmp, "tr"))
    st = start_storage(os.path.join(tmp, "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu", extra=SCRUB)
    cli = FdfsClient([f"127.0.0.1:{tr.port}"])
    base = os.path.join(tmp, "st")

    def recipes():
        # Slab-aware: flat .rcp sidecars OR live slab recipe records.
        return sorted(recipe_keys(base))

    def recipe_bytes():
        flat = glob.glob(os.path.join(base, "data", "**", "*.rcp"),
                         recursive=True)
        if flat:
            return os.path.getsize(flat[0])
        live = [r for r in slab_records(base)
                if r["kind"] == 2 and not r["dead"]]
        return live[0]["payload_len"] if live else 0

    try:
        data = os.urandom(200 << 10)
        fid = upload_retry(cli, data, ext="bin")
        assert _wait(recipes), "chunk-eligible upload left no recipe"
        rcp_bytes = recipe_bytes()
        assert rcp_bytes > 0
        cli.delete_file(fid)
        assert _wait(lambda: not recipes()), "recipe sidecar leaked"
        status = cli.scrub_status("127.0.0.1", st.port)
        assert status["recipes_reclaimed"] == 1
        assert status["bytes_reclaimed"] >= rcp_bytes
    finally:
        st.stop()
        tr.stop()


@needs_native
def test_scrub_races_uploads_and_deletes(tmp_path):
    """Scrub/GC passes racing live traffic (the TSan target): constant
    negotiated uploads + deletes while kicks force back-to-back passes
    with a zero grace window.  Nothing may crash, and every surviving
    file must still download byte-identical afterwards."""
    from fastdfs_tpu.client import FdfsClient

    tmp = str(tmp_path)
    tr = start_tracker(os.path.join(tmp, "tr"))
    st = start_storage(os.path.join(tmp, "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu",
                       extra=HB + "\nscrub_interval_s = 0"
                             "\nchunk_gc_grace_s = 0")
    addr = f"127.0.0.1:{tr.port}"
    base = os.urandom(96 << 10)
    upload_retry(FdfsClient([addr]), b"warmup" * 64)
    stop = threading.Event()
    errors: list[str] = []
    kept: dict[str, bytes] = {}
    lock = threading.Lock()

    def uploader():
        cli = FdfsClient([addr])
        i = 0
        while not stop.is_set():
            # shared head (dedup + shared chunks), unique tail
            data = base + os.urandom(32 << 10)
            try:
                fid = cli.upload_buffer_dedup(data, ext="bin",
                                              min_dup_ratio=0)
                with lock:
                    kept[fid] = data
            except Exception as e:  # noqa: BLE001
                errors.append(f"upload: {e}")
                return
            i += 1

    def deleter():
        cli = FdfsClient([addr])
        while not stop.is_set():
            with lock:
                doomed = next(iter(kept), None)
                data = kept.pop(doomed, None)
            del data
            if doomed is None:
                time.sleep(0.05)
                continue
            try:
                cli.delete_file(doomed)
            except Exception as e:  # noqa: BLE001
                errors.append(f"delete: {e}")
                return

    def kicker():
        cli = FdfsClient([addr])
        while not stop.is_set():
            try:
                cli.scrub_kick("127.0.0.1", st.port)
            except Exception as e:  # noqa: BLE001
                errors.append(f"kick: {e}")
                return
            time.sleep(0.1)

    threads = [threading.Thread(target=f)
               for f in (uploader, deleter, kicker)]
    try:
        for t in threads:
            t.start()
        time.sleep(6.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    try:
        assert not errors, errors
        assert st.proc.poll() is None, "storage daemon died under scrub race"
        cli = FdfsClient([addr])
        status = cli.scrub_status("127.0.0.1", st.port)
        assert status["passes"] >= 1
        # No false corruption: live chunks re-hashed clean under load.
        assert status["chunks_corrupt"] == 0, status
        with lock:
            survivors = dict(kept)
        assert survivors, "race produced no surviving files"
        for fid, data in list(survivors.items())[:5]:
            assert cli.download_to_buffer(fid) == data
    finally:
        st.stop()
        tr.stop()


@needs_native
@pytest.mark.parametrize("mode,conf", [
    ("off", "scrub_interval_s = 0"),
    ("paced", "scrub_interval_s = 1\nscrub_bandwidth_mb_s = 16"),
    ("unpaced", "scrub_interval_s = 1\nscrub_bandwidth_mb_s = 0")])
def test_timed_passes_verify_under_foreground_io(tmp_path, mode, conf):
    """No kick: the scrubber's own timer re-verifies the chunk store while
    uploads, downloads and deletes run, paced or not, and flags nothing
    that is sound; with the timer off nothing is verified."""
    from fastdfs_tpu.client import FdfsClient

    tmp = str(tmp_path)
    tr = start_tracker(os.path.join(tmp, "tr"))
    st = start_storage(os.path.join(tmp, "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu", extra=HB + "\n" + conf)
    cli = FdfsClient([f"127.0.0.1:{tr.port}"])
    try:
        upload_retry(cli, b"warmup" * 64)
        kept = {}
        for _ in range(4):
            data = os.urandom(256 << 10)
            kept[cli.upload_buffer(data, ext="bin")] = data
        deadline = time.time() + 3.0
        ops = 0
        while time.time() < deadline or ops < 10:
            data = os.urandom(64 << 10)
            fid = cli.upload_buffer(data, ext="bin")
            assert cli.download_to_buffer(fid) == data
            cli.delete_file(fid)
            ops += 1
        status = cli.scrub_status("127.0.0.1", st.port)
        if mode == "off":
            assert status["passes"] == 0 and status["chunks_verified"] == 0
        else:
            assert status["passes"] >= 1, status
            assert status["chunks_verified"] > 0
            assert status["bytes_verified"] > 0
        assert status["chunks_corrupt"] == 0, status
        for fid, data in kept.items():
            assert cli.download_to_buffer(fid) == data
    finally:
        cli.close()
        st.stop()
        tr.stop()
