"""Localhost cluster harness: spawn C++ daemons as subprocesses.

SURVEY.md §4: every port and path is config, so a pytest harness can spin
up 1 tracker + N storages on localhost — the multi-node testing story the
reference only supported manually.
"""

from __future__ import annotations

import contextlib
import fcntl
import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# FDFS_NATIVE_BUILD selects an alternate build tree (the sanitizer
# builds from tools/run_sanitizers.sh use native/build-asan etc.).
BUILD = os.path.join(REPO, os.environ.get("FDFS_NATIVE_BUILD",
                                          os.path.join("native", "build")))
STORAGED = os.path.join(BUILD, "fdfs_storaged")
TRACKERD = os.path.join(BUILD, "fdfs_trackerd")
# Every executable of native/CMakeLists.txt: a tree is built when all of
# them are there (ninja links them last, so a half-done build never passes).
BINARIES = tuple(os.path.join(BUILD, name) for name in (
    "fdfs_storaged", "fdfs_trackerd", "fdfs_codec", "fdfs_load",
    "common_test", "storage_test", "tracker_test"))


def ensure_native_built(targets: tuple[str, ...] = ()) -> None:
    """Build the native tree (cmake + ninja, every target) unless every
    executable is already there.  The one build routine: the tests (once
    per session, tests/conftest.py) and chip_smoke.py both come through
    here."""
    wanted = (*BINARIES, *targets)
    if all(os.path.exists(t) for t in wanted):
        return
    # An alternate tree implies an instrumented build
    # (tools/run_sanitizers.sh naming); configuring it without the
    # matching flags would silently produce uninstrumented binaries that
    # "pass" the sanitizer suite.  build-lockrank is TSan + the
    # FDFS_LOCKRANK rank checker (common/lockrank.h).
    base = os.path.basename(BUILD)
    sanitize, lockrank = "", False
    if base.startswith("build-"):
        flavor = base[len("build-"):]
        if flavor == "lockrank":
            sanitize, lockrank = "thread", True
        else:
            sanitize = {"asan": "address", "tsan": "thread",
                        "ubsan": "undefined"}.get(flavor, "")
            if not sanitize:
                raise RuntimeError(
                    f"unknown sanitizer build dir {base!r}: "
                    f"build it explicitly")
    # pytest-xdist workers all arrive here at once on a fresh checkout:
    # the first to take the lock builds, the rest wait and then find the
    # binaries.  Unserialized, N workers ran N interleaved ninja builds
    # into the one directory.
    with open(BUILD + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if all(os.path.exists(t) for t in wanted):
            return
        for cmd in (["cmake", "-S", os.path.join(REPO, "native"), "-B", BUILD,
                     "-G", "Ninja", f"-DSANITIZE={sanitize}",
                     f"-DFDFS_LOCKRANK={'ON' if lockrank else 'OFF'}"],
                    ["ninja", "-C", BUILD]):
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"native build failed ({cmd[0]} exit "
                    f"{proc.returncode}):\n"
                    f"{(proc.stdout + proc.stderr)[-4000:]}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_port(port: int, timeout: float = 10.0, host: str = "127.0.0.1") -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            with socket.create_connection((host, port), timeout=1):
                return
        except OSError:
            time.sleep(0.05)
    raise TimeoutError(f"{host}:{port} never came up")


class Daemon:
    def __init__(self, binary: str, conf_path: str, port: int,
                 ip: str = "127.0.0.1"):
        # Daemon output goes to FILES, never PIPE: with log_level=debug
        # the daemons log to stderr, and an undrained 64 KB pipe buffer
        # eventually BLOCKS the daemon mid-write (heartbeats stall, the
        # tracker marks it OFFLINE, and tests that pass in isolation —
        # fewer log lines — flake under suite load).
        self._out_path = conf_path + ".stdout"
        self._err_path = conf_path + ".stderr"
        with open(self._out_path, "ab") as out_f, \
                open(self._err_path, "ab") as err_f:
            self.proc = subprocess.Popen(
                [binary, conf_path], stdout=out_f, stderr=err_f)
        self.port = port
        self.ip = ip
        try:
            # Generous under suite load: a busy machine (sidecar JAX
            # compiles in sibling tests) can stretch daemon startup well
            # past an unloaded run's.
            wait_port(port, host=ip, timeout=30.0)
        except TimeoutError:
            self.proc.kill()
            self.proc.wait()
            raise RuntimeError(
                f"daemon failed to start:\nstdout: {self.stdout_text}\n"
                f"stderr: {self.stderr_text}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()

    def _read(self, path: str) -> str:
        try:
            with open(path, "rb") as fh:
                return fh.read().decode(errors="replace")
        except OSError:
            return ""

    @property
    def stdout_text(self) -> str:
        return self._read(self._out_path)

    @property
    def stderr_text(self) -> str:
        return self._read(self._err_path)


def make_storage_conf(base_dir: str, port: int, group: str = "group1",
                      trackers: list[str] | None = None,
                      subdirs: int = 4, dedup_mode: str = "none",
                      dedup_sidecar: str = "", extra: str = "",
                      ip: str = "127.0.0.1") -> str:
    conf = os.path.join(base_dir, "storage.conf")
    lines = [
        f"group_name = {group}",
        f"bind_addr = {ip}",
        f"port = {port}",
        f"base_path = {base_dir}",
        f"store_path0 = {base_dir}",
        f"subdir_count_per_path = {subdirs}",
        f"dedup_mode = {dedup_mode}",
        "log_level = debug",
    ]
    if dedup_sidecar:
        lines.append(f"dedup_sidecar = {dedup_sidecar}")
    for t in trackers or []:
        lines.append(f"tracker_server = {t}")
    if extra:
        lines.append(extra)
    with open(conf, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    return conf


def start_storage(tmp_path, port: int | None = None, ip: str = "127.0.0.1",
                  **kw) -> Daemon:
    ensure_native_built()
    port = port or free_port()
    base = str(tmp_path)
    os.makedirs(base, exist_ok=True)
    conf = make_storage_conf(base, port, ip=ip, **kw)
    return Daemon(STORAGED, conf, port, ip=ip)


def make_tracker_conf(base_dir: str, port: int, store_lookup: int = 0,
                      check_active: int = 3, extra: str = "") -> str:
    conf = os.path.join(base_dir, "tracker.conf")
    lines = [
        "bind_addr = 127.0.0.1",
        f"port = {port}",
        f"base_path = {base_dir}",
        f"store_lookup = {store_lookup}",
        f"check_active_interval = {check_active}",
        "save_interval = 2",
        "log_level = debug",
    ]
    if extra:
        lines.append(extra)
    with open(conf, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    return conf


def start_tracker(tmp_path, port: int | None = None, **kw) -> Daemon:
    ensure_native_built((TRACKERD,))
    port = port or free_port()
    base = str(tmp_path)
    os.makedirs(base, exist_ok=True)
    conf = make_tracker_conf(base, port, **kw)
    return Daemon(TRACKERD, conf, port)


class Sidecar:
    """One ``python -m fastdfs_tpu.sidecar`` child, started as an operator
    starts it: it takes the chip, and exits at once if there is none.
    ``extra_args`` is where CPU rehearsals put ``--platform cpu``.  The
    caller's process must not have initialised a JAX backend: the sidecar
    is the one process that may hold the device."""

    def __init__(self, base: str, extra_args: tuple[str, ...] = (),
                 state_dir: str | None = None):
        os.makedirs(base, exist_ok=True)
        self.sock = os.path.join(base, "dedup.sock")
        self._sock_dir = None
        if len(self.sock.encode()) > 100:  # sun_path holds 108 bytes
            self._sock_dir = tempfile.mkdtemp(prefix="fdfs_sc_")
            self.sock = os.path.join(self._sock_dir, "dedup.sock")
        self.log_path = os.path.join(base, "sidecar.log")
        args = [sys.executable, "-m", "fastdfs_tpu.sidecar",
                "--socket", self.sock, *extra_args]
        if state_dir:
            os.makedirs(state_dir, exist_ok=True)
            args += ["--state-dir", state_dir]
        with contextlib.suppress(FileNotFoundError):
            os.unlink(self.sock)
        t0 = time.monotonic()
        with open(self.log_path, "ab") as log:
            self.proc = subprocess.Popen(args, cwd=REPO, stdout=log,
                                         stderr=subprocess.STDOUT)
        # It listens only after warm-up has compiled every bucket shape
        # (a cold one takes half a minute on a v5e, minutes on XLA's CPU
        # backend; later starts find them in the compile cache).
        while True:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"sidecar exited {self.proc.returncode} before it "
                    f"listened: {self.log_tail()}")
            if os.path.exists(self.sock):
                with contextlib.suppress(OSError):  # bound, not listening yet
                    self.stats()
                    break
            if time.monotonic() > t0 + 1800:
                self.stop()
                raise TimeoutError("sidecar did not listen within 1800 s: "
                                   + self.log_tail())
            time.sleep(0.2)
        self.ready_s = time.monotonic() - t0

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as fh:
            return fh.read()

    def log_tail(self) -> str:
        return " | ".join(self.log_text().strip().splitlines()[-6:])

    def warmup_s(self) -> float:
        """engine.warmup() seconds, as the sidecar itself logged them."""
        for line in reversed(self.log_text().splitlines()):
            if "dedup sidecar warmed in" in line:
                return float(line.split("warmed in")[1].split("s")[0])
        raise RuntimeError("sidecar logged no warm-up time")

    def stats(self) -> dict:
        """Its ``stats`` reply: counters + the device it really got."""
        from fastdfs_tpu.sidecar import read_stats
        return read_stats(self.sock)

    def rss_mb(self) -> float:
        with open(f"/proc/{self.proc.pid}/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
        raise RuntimeError("the sidecar's /proc status has no VmRSS")

    def stop(self) -> None:
        """SIGTERM: the sidecar snapshots its state on the way out."""
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if self._sock_dir:
            shutil.rmtree(self._sock_dir, ignore_errors=True)


def chunk_files(base_dir: str) -> list[str]:
    """Every FLAT content-addressed chunk payload file under a storage's
    base dir (``<base>/data/chunks/<d0d1>/<d2d3>/<40-hex>``).  Chunks
    below ``slab_chunk_threshold`` live inside slab files instead — use
    :func:`chunk_digests` for the layout-agnostic inventory."""
    import glob
    return sorted(
        f for f in glob.glob(os.path.join(str(base_dir), "data", "chunks",
                                          "*", "*", "*"))
        if os.path.isfile(f) and len(os.path.basename(f)) == 40)


# -- slab store parsing (native/storage/slabstore.h record layout) ----------
# Per record: 4s magic "FSLB", u8 version, u8 kind (1 chunk | 2 recipe),
# u8 flags (bit0 dead), u8 key_len, u64 alloc_len, u64 payload_len,
# u32 payload_crc32, u64 mtime, u32 header_crc32 (flags zeroed), then key
# and payload.  Pinned cross-language by `fdfs_codec slab-layout`.
SLAB_HEADER = ">4sBBBBqqIqI"
SLAB_HEADER_SIZE = 40
SLAB_KIND_CHUNK, SLAB_KIND_RECIPE = 1, 2


def slab_files(base_dir: str) -> list[str]:
    import glob
    return sorted(glob.glob(os.path.join(str(base_dir), "data", "slabs",
                                         "*.slab")))


def slab_records(base_dir: str) -> list[dict]:
    """Scan every slab file's record headers (the same walk the daemon's
    boot rescan does).  Returns dicts with kind/key/flags/payload
    offsets — the slot-index dump the slab-aware test helpers build on.
    Stops at the first unparseable record of a file (torn tail)."""
    import struct
    import zlib
    out = []
    for path in slab_files(base_dir):
        with open(path, "rb") as fh:
            blob = fh.read()
        off = 0
        while off + SLAB_HEADER_SIZE <= len(blob):
            (magic, ver, kind, flags, key_len, alloc_len, payload_len,
             payload_crc, mtime, header_crc) = struct.unpack_from(
                SLAB_HEADER, blob, off)
            hdr = bytearray(blob[off:off + 36])
            hdr[6] = 0  # header CRC is computed with flags zeroed
            if (magic != b"FSLB" or ver != 1
                    or zlib.crc32(bytes(hdr)) & 0xFFFFFFFF != header_crc
                    or off + SLAB_HEADER_SIZE + key_len + alloc_len
                    > len(blob)):
                break  # torn tail
            key = blob[off + SLAB_HEADER_SIZE:
                       off + SLAB_HEADER_SIZE + key_len]
            out.append({
                "path": path,
                "kind": kind,
                "key": key.decode("latin-1"),
                "flags": flags,
                "dead": bool(flags & 1),
                "record_off": off,
                "payload_off": off + SLAB_HEADER_SIZE + key_len,
                "payload_len": payload_len,
                "payload_crc32": payload_crc,
                "mtime": mtime,
            })
            off += SLAB_HEADER_SIZE + key_len + alloc_len
    return out


def chunk_digests(base_dir: str) -> dict[str, int]:
    """Layout-agnostic live-chunk inventory: ``{digest: byte length}``
    across flat chunk files AND live slab records.  The slab-aware twin
    of :func:`chunk_files` (newest slab record wins a duplicate key,
    matching the daemon's boot-rescan resolution)."""
    inv = {os.path.basename(f): os.path.getsize(f)
           for f in chunk_files(base_dir)}
    # One ordered walk; the LAST record for a key is authoritative (a
    # replace appends the new copy before the old record's dead mark).
    latest: dict[str, tuple[bool, int]] = {}
    for rec in slab_records(base_dir):
        if rec["kind"] == SLAB_KIND_CHUNK:
            latest[rec["key"]] = (rec["dead"], rec["payload_len"])
    for key, (dead, length) in latest.items():
        if not dead:
            inv[key] = length
        # A dead slab record does NOT erase a flat twin: the daemon's
        # read path falls back to the flat file when the slot index
        # misses (heal/repair in drain mode writes flat + kills the
        # slab record), so a flat-backed digest stays live here too.
    return inv


def recipe_keys(base_dir: str) -> set[str]:
    """Live recipe identities across both layouts: basenames of flat
    ``*.rcp`` sidecars plus live slab recipe-record keys' basenames."""
    import glob
    names = {os.path.basename(p) for p in glob.glob(
        os.path.join(str(base_dir), "data", "**", "*.rcp"), recursive=True)}
    latest: dict[str, bool] = {}
    for rec in slab_records(base_dir):
        if rec["kind"] == SLAB_KIND_RECIPE:
            latest[rec["key"]] = rec["dead"]
    for key, dead in latest.items():
        if not dead:
            names.add(os.path.basename(key))
    return names


def read_chunk_payload(base_dir: str, digest: str) -> bytes:
    """The live payload bytes of one chunk, whichever layout holds it
    (flat file, or the newest live slab record)."""
    flat = os.path.join(str(base_dir), "data", "chunks", digest[:2],
                        digest[2:4], digest)
    if os.path.isfile(flat):
        with open(flat, "rb") as fh:
            return fh.read()
    target = None
    for rec in slab_records(base_dir):
        if (rec["kind"] == SLAB_KIND_CHUNK and rec["key"] == digest
                and not rec["dead"]):
            target = rec
    if target is None:
        raise FileNotFoundError(f"no live payload for {digest} under "
                                f"{base_dir}")
    with open(target["path"], "rb") as fh:
        fh.seek(target["payload_off"])
        return fh.read(target["payload_len"])


def corrupt_chunk(base_dir: str, digest: str | None = None) -> tuple[str, str]:
    """Flip one byte inside a stored chunk payload — the bit-rot
    injection for scrub tests.  Slab-aware: flat chunk files are
    patched in place as before; a slab-resident chunk is located via
    the record-header scan and its payload byte flipped inside the slab
    file.  Picks the first live chunk (or the named ``digest``);
    returns ``(digest, path)``.  Lengths are preserved so only the
    content hash betrays the damage."""
    if digest is not None:
        path = os.path.join(str(base_dir), "data", "chunks", digest[:2],
                            digest[2:4], digest)
        if os.path.isfile(path):
            files = [path]
        else:
            files = []
    else:
        files = chunk_files(base_dir)
    if files:
        path = files[0]
        with open(path, "r+b") as fh:
            first = fh.read(1)
            fh.seek(0)
            fh.write(bytes([first[0] ^ 0xFF]))
        return os.path.basename(path), path
    # Slab-resident: the newest LIVE record for the digest (or, with no
    # digest named, the last live chunk record in scan order).
    target = None
    for rec in slab_records(base_dir):
        if (rec["kind"] != SLAB_KIND_CHUNK or rec["payload_len"] <= 0
                or rec["dead"]):
            continue
        if digest is not None and rec["key"] != digest:
            continue
        target = rec
    if target is None:
        raise FileNotFoundError(f"no chunk payload for {digest!r} under "
                                f"{base_dir}")
    with open(target["path"], "r+b") as fh:
        fh.seek(target["payload_off"])
        first = fh.read(1)
        fh.seek(target["payload_off"])
        fh.write(bytes([first[0] ^ 0xFF]))
    return target["key"], target["path"]


# -- EC stripe parsing (native/storage/ecstore.cc on-disk layout) -----------
# Shard file <base>/data/ec/<%010d>.s<%02d>: 52-byte header — 8s magic
# "FDFSECS1", i64 stripe_id, u32 shard_idx, u32 k, u32 m, i64 shard_len,
# i64 data_len, u32 payload crc32, u32 header crc32 (of the first 48
# bytes) — then shard_len payload bytes.  Manifest <%010d>.mft: 8s magic
# "FDFSECM1", u32 k, u32 m, i64 shard_len, i64 data_len, i64 chunk_count,
# then per chunk 20s raw digest + i64 offset + i64 length + u8 dead, then
# a trailing crc32 of everything before it.  All big-endian; pinned
# cross-language by `fdfs_codec ec-stripe-layout`.
EC_SHARD_HEADER = ">8sqIIIqqII"
EC_SHARD_HEADER_SIZE = 52
EC_MANIFEST_FIXED = 40
EC_MANIFEST_PER_CHUNK = 37


def stripe_files(base_dir: str) -> dict[int, dict]:
    """EC stripe inventory under ``<base>/data/ec/``: per stripe id, the
    manifest-decoded geometry + live chunk map and every shard file
    present on disk — ``{id: {"k", "m", "shard_len", "data_len",
    "chunks": {digest: (offset, length, dead)}, "shards": {idx: path},
    "manifest": path}}``.  Stripes whose manifest fails its CRC are
    skipped, matching the daemon's boot-rescan behavior."""
    import glob
    import struct
    import zlib
    ec_dir = os.path.join(str(base_dir), "data", "ec")
    out: dict[int, dict] = {}
    for path in sorted(glob.glob(os.path.join(ec_dir, "*.mft"))):
        sid = int(os.path.basename(path)[:10])
        with open(path, "rb") as fh:
            blob = fh.read()
        if (len(blob) < EC_MANIFEST_FIXED + 4 or blob[:8] != b"FDFSECM1"
                or zlib.crc32(blob[:-4]) & 0xFFFFFFFF
                != struct.unpack(">I", blob[-4:])[0]):
            continue
        k, m = struct.unpack_from(">II", blob, 8)
        shard_len, data_len, count = struct.unpack_from(">qqq", blob, 16)
        chunks: dict[str, tuple[int, int, bool]] = {}
        for c in range(count):
            off = EC_MANIFEST_FIXED + c * EC_MANIFEST_PER_CHUNK
            raw = blob[off:off + 20]
            coff, clen = struct.unpack_from(">qq", blob, off + 20)
            chunks[raw.hex()] = (coff, clen, blob[off + 36] != 0)
        shards = {}
        for sp in sorted(glob.glob(os.path.join(
                ec_dir, f"{sid:010d}.s[0-9][0-9]"))):
            shards[int(sp[-2:])] = sp
        out[sid] = {"k": k, "m": m, "shard_len": shard_len,
                    "data_len": data_len, "chunks": chunks,
                    "shards": shards, "manifest": path}
    return out


def shard_digests(base_dir: str) -> dict[str, tuple[int, int]]:
    """Layout map of EC-resident chunks: ``{digest: (stripe_id,
    chunk_index)}`` across every live manifest slot — the EC twin of
    :func:`chunk_digests` for asserting demotion coverage."""
    out: dict[str, tuple[int, int]] = {}
    for sid, st in stripe_files(base_dir).items():
        for i, (digest, (_, _, dead)) in enumerate(st["chunks"].items()):
            if not dead:
                out[digest] = (sid, i)
    return out


def corrupt_shard(base_dir: str, stripe_id: int | None = None,
                  shard_idx: int | None = None,
                  delete: bool = False) -> tuple[int, int, str]:
    """Shard-loss injection for reconstruction tests: flip one payload
    byte inside (or with ``delete=True`` unlink) one shard file of one
    stripe.  Defaults to the first stripe's first present shard; returns
    ``(stripe_id, shard_idx, path)``.  A flip leaves the 52-byte header
    intact so only the payload CRC betrays the damage — the same failure
    scrub's VerifyRepairStripe is built to catch."""
    stripes = stripe_files(base_dir)
    if not stripes:
        raise FileNotFoundError(f"no EC stripes under {base_dir}")
    sid = stripe_id if stripe_id is not None else sorted(stripes)[0]
    shards = stripes[sid]["shards"]
    if not shards:
        raise FileNotFoundError(f"stripe {sid} has no shard files left")
    idx = shard_idx if shard_idx is not None else sorted(shards)[0]
    path = shards[idx]
    if delete:
        os.unlink(path)
        return sid, idx, path
    with open(path, "r+b") as fh:
        fh.seek(EC_SHARD_HEADER_SIZE)
        first = fh.read(1)
        fh.seek(EC_SHARD_HEADER_SIZE)
        fh.write(bytes([first[0] ^ 0xFF]))
    return sid, idx, path


def upload_retry(cli, data, timeout=20.0, **kw):
    """Upload with retries while a fresh daemon joins/activates (the
    tracker refuses query_store until the storage reports in)."""
    deadline = time.time() + timeout
    while True:
        try:
            return cli.upload_buffer(data, **kw)
        except Exception:
            if time.time() >= deadline:
                raise
            time.sleep(0.5)
