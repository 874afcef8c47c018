"""Dedup sidecar: the TPU fingerprint engine behind the storage daemon.

This is the server half of the daemon's ``dedup_mode = sidecar`` plugin
(C++ client: ``native/storage/dedup.cc:SidecarDedup``): a unix-socket
service speaking the standard 10-byte framing with the DEDUP_* opcodes.
The storage daemon streams each chunk-eligible upload through cmd 120 and
writes only the chunks its content-addressed store has never seen — this
process supplies the cut-points and digests, computed by the JAX/TPU
pipeline (position-parallel gear CDC + batched SHA1; the replacement for
the scalar CRC32 loop in the reference's
``storage/storage_dio.c:dio_write_file()``).

Opcodes
-------
* ``DEDUP_FINGERPRINT`` (120): body = 8B BE session id + 8B BE
  base_offset + raw segment bytes.  Response: 8B BE chunk count, then
  per chunk 8B BE offset + 8B BE length + 20B raw SHA1.  The session id
  (minted by the daemon per upload — ``SidecarDedup::BeginChunked``)
  scopes ALL pending state: the accumulated file signature and the
  per-chunk digest attributions stay buffered under the session until
  commit/abort, so concurrent uploads cannot interleave and nothing
  provisional ever reaches the indexes or their snapshots.
* ``DEDUP_QUERY`` (121): body = 40-hex whole-file SHA1.  Response: the
  canonical file id if known (whole-file dedup for sub-threshold files).
* ``DEDUP_COMMIT`` (122): text body, one of
  ``commitfile <sha1hex> <file_id>`` |
  ``commitchunks <session> <file_id>`` | ``abort <session>`` |
  ``forget <file_id>`` | ``stats`` | ``widths <min> <avg_bits> <max>``.
  ``widths`` is how a daemon opens every connection: the chunk widths it
  cuts with (storage.conf ``dedup_cdc_widths``).  This engine plans its
  tiles for, and its indexes hold chunks of, one set of widths
  (``--cdc-widths``); any other set is answered status 22 with both sets
  in the body and a line in the log, and the daemon then fingerprints
  nothing over that connection: no recipe is stored under mixed widths.
  ``abort`` is sent on flat-fallback
  or a failed upload; sessions older than ``_SESSION_TTL`` seconds are
  reaped in case a daemon dies without either message.  ``stats``
  returns the service counters as JSON: ``fingerprint_bytes``,
  ``chunks``, ``requests``, ``verify_host_fallbacks``; ``engine_us``,
  the time inside ``DedupEngine.fingerprint``, which runs OUTSIDE
  ``_lock`` so that concurrent uploads overlap on the device;
  ``lock_wait_us``, the wait for ``_lock`` AFTER that call, to append
  the reply and the session's bookkeeping (it cannot show queueing for
  the engine: nothing queues there; what it waits behind is mostly the
  other holders, the commits: span ``fdfs.sidecar.commit``, which puts
  a session's digests into the exact index in one batch, and
  ``fdfs.exact.merge`` when that batch folds the index's delta into its
  base); ``exact_insert_batches``, ``exact_inserted``,
  ``exact_merges``, the exact index's counters (batches are commits,
  inserted the digests they added); ``span_us`` / ``span_n``, wall time
  and count of every ``fdfs.*`` span by name (``dedup/spans.py``; the
  table is in OPERATIONS.md, "Tracing"; ``engine_us`` and
  ``lock_wait_us`` are two of its entries under their old names);
  ``host_stall_us``, counted while a trace runs: wall minus thread CPU
  time over the spans in which a request thread has only Python to run
  (parse, pack, scatter, reply), i.e. the time it waited for the
  interpreter or a core; ``recv_calls`` / ``recv_bytes``, the
  ``recv_into`` calls the fingerprint bodies took and their bytes (one
  call a body when the whole of it is waited for inside the kernel).
  Plus the device this process got (backend,
  device_kind, device_count, use_pallas, fan_out, device_bytes per
  device id, tiles_by_rows, the tiles placed there by their row count,
  and memory_peak_bytes, the most the fullest device has held), the
  chunk widths in force (``widths``) and the SHA-1 launches summed over
  every tile: ``rows_placed`` (rows that held a chunk),
  ``lanes_launched`` (the rows after the kernel's padding),
  ``sha1_grid_steps`` (64-byte blocks walked one after another: a tile
  under 128 rows ends at its longest chunk) and ``sha1_width_steps``
  (the blocks of the tiles' widths: walked / width is the share of the
  widths' walk that is left) — a reader learns from it whether the chip
  did the work, which the daemon's fail-open path would otherwise hide;
  and the tiles' pack: ``pack_rows``, ``pack_rows_released`` (rows
  copied by a call that lets the interpreter go), ``pack_copied_bytes``
  and ``pack_zeroed_bytes``.
  ``trace start <dir>`` / ``trace stop`` start and stop a JAX profiler
  trace of this process (the one that holds the chip) into ``<dir>``:
  device operations and the ``fdfs.*`` spans on one clock.  Both are
  answered before ``_lock`` is taken (starting a trace takes seconds);
  a second ``trace start`` while one runs gets status 16 (EBUSY).  The
  socket is trusted: whoever may connect may already ``forget`` a file,
  and may have a profile written wherever this process may write.
* ``DEDUP_NEARDUPS`` (123): body = file id text.  Response: ranked text
  lines ``<file_id> <score>`` from the near-dup index on the device
  (``dedup/near_index.py``; behind the daemon's ``NEAR_DUPS`` command):
  score descending, ties older file first, exact over every row
  acknowledged before the query was sent.  The query runs outside
  ``_lock`` and joins the index's next pass, which answers every query
  waiting when it starts.  Status 61 when the file carries no signature.
  ``stats`` reports the index as ``near_queries``, ``near_scans``,
  ``near_scan_us``, ``near_rank_spills``, ``near_inserts``,
  ``near_removed`` (counters) and
  ``near_rows``, ``near_base_rows``, ``near_resident_bytes`` (gauges).
* ``DEDUP_VERIFY`` (136): batched chunk-integrity verify for the storage
  scrubber (``native/storage/scrub.cc``).  Body = 8B count + per chunk
  (8B length + 20B expected raw SHA1) + payloads concatenated; response
  = count bytes (0 = match, 1 = mismatch).  Hashing runs on the
  accelerator via ``ops/sha1.sha1_batch`` (a device failure is answered
  from hashlib and counted in ``verify_host_fallbacks``); the daemon
  falls back to its serial host SHA1 when this RPC is unavailable.
* ``DEDUP_FINGERPRINT_CUTS`` (125): DEDUP_FINGERPRINT with the cut
  offsets precomputed by the caller's native CDC (8B session + 8B
  base_offset + 8B n_cuts + n_cuts x 8B ends + bytes) — the production
  daemon path: chunking stays on the CPU (AVX2, identical cut points),
  the accelerator round-trip only carries the hash work.
* ``DEDUP_EC_ENCODE`` (150): the parity of one RS(k, m) stripe for the
  daemon's erasure coding on write.  Body = 8B BE k + 8B BE m + 8B BE
  shard_len + the k data shards; response = the m parity shards.  Pure
  compute outside ``_lock``: the shards go to the device through the
  engine's staging pool and ``ops/pallas_gf.py``'s kernel computes the
  parity (``DedupEngine.ec_encode``), inside one ``fdfs.ec.encode`` span
  (``k``, ``m``, ``shard_len``, ``bytes``: the data shards' bytes).
  ``stats`` counts ``ec_encode_requests``, ``ec_encode_bytes`` (the same
  bytes; the daemon's ``STAT`` counter ``ec.write_bytes`` counts them
  too, so the two agree when the chip computed every parity byte) and
  ``ec_encode_us`` (the span's wall time).  Status 22 on a body that does
  not hold its geometry.

State: whole-file digest map + the DedupEngine's exact and near-dup
indexes; snapshotted to ``<state_dir>/sidecar_*`` on SIGTERM and every
``--snapshot-interval`` seconds.  The near-dup snapshot holds the rows
this process indexed and the spec of its ``--near-base``, never the base:
nothing is copied off the device.

Run: ``python -m fastdfs_tpu.sidecar --socket /path/dedup.sock``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import struct
import sys
import threading
import time

import numpy as np

from fastdfs_tpu.common.ini_config import parse_bytes
from fastdfs_tpu.common.protocol import HEADER_SIZE, StorageCmd, unpack_header
from fastdfs_tpu.dedup.engine import DedupConfig, DedupEngine
from fastdfs_tpu.dedup.spans import mark, new_acc, span

_I64 = struct.Struct(">q")
_I64X2 = struct.Struct(">qq")
_FINGERPRINT_CMDS = (StorageCmd.DEDUP_FINGERPRINT,
                     StorageCmd.DEDUP_FINGERPRINT_CUTS)
# Opcodes whose body goes to the handler as a view of the receive buffer.
_VIEW_CMDS = _FINGERPRINT_CMDS + (StorageCmd.DEDUP_EC_ENCODE,)
_I64X3 = struct.Struct(">qqq")

# A session the daemon opened to re-index bytes it already stores (a
# negotiated upload's commit, a recovered file) carries this bit
# (dedup.h, kDedupReindexSessionBit): the root span says `reindex=1` and
# the bytes are also counted apart, so device time can be split by who asked.
REINDEX_SESSION_BIT = 1 << 62

_SESSION_TTL = 600.0  # seconds before an uncommitted session is reaped
_SHIPPED_WIDTHS = (DedupConfig.min_size, DedupConfig.avg_bits,
                   DedupConfig.max_size)


# A fingerprint reply's record of one chunk: offset, length, raw SHA1.
_CHUNK_REC = np.dtype([("off", ">i8"), ("len", ">i8"), ("dig", "V20")])


class _Session:
    """Pending per-upload state: accumulated file signature + the digest
    attributions to insert (with the real file id) at commit time, one
    entry per fingerprint request: its raw digests (20 B a chunk) and the
    chunks' absolute offsets."""

    __slots__ = ("sig", "digests", "touched")

    def __init__(self) -> None:
        self.sig: np.ndarray | None = None
        self.digests: list[tuple[bytes, np.ndarray]] = []
        self.touched = time.monotonic()


def _pack_header(pkg_len: int, cmd: int, status: int = 0) -> bytes:
    return struct.pack(">qBB", pkg_len, cmd, status)


def rpc(socket_path: str, cmd: int, body: bytes = b"",
        timeout: float = 120.0) -> tuple[int, bytes]:
    """One request to a running sidecar, as the daemon sends it:
    returns ``(status, response body)``."""
    with socket.socket(socket.AF_UNIX, socket.SOCK_STREAM) as s:
        s.settimeout(timeout)
        s.connect(socket_path)
        s.sendall(_pack_header(len(body), cmd) + body)
        hdr = DedupSidecar._recv_exact(s, HEADER_SIZE)
        if hdr is None:
            raise OSError("sidecar closed the connection")
        h = unpack_header(hdr)
        resp = DedupSidecar._recv_exact(s, h.pkg_len) if h.pkg_len else b""
        if resp is None:
            raise OSError("sidecar closed mid-response")
        return h.status, resp


def read_stats(socket_path: str) -> dict:
    """The ``stats`` reply: service counters + the device the sidecar got."""
    status, resp = rpc(socket_path, StorageCmd.DEDUP_COMMIT, b"stats")
    if status != 0:
        raise OSError(f"sidecar stats: status {status}")
    return json.loads(resp)


def parse_widths(text: str) -> tuple[int, int, int]:
    """``<min>:<avg_bits>:<max>`` as storage.conf's ``dedup_cdc_widths``
    writes it (sizes take a K, M or G suffix) → (min, avg_bits, max)."""
    parts = text.strip().split(":")
    if len(parts) != 3:
        raise ValueError(f"widths {text!r}: want <min>:<avg_bits>:<max>")
    return parse_bytes(parts[0]), int(parts[1]), parse_bytes(parts[2])


def parse_near_base(text: str) -> tuple[int, int]:
    """``<rows>:<seed>`` of ``--near-base`` → (rows, seed)."""
    rows, _, seed = text.partition(":")
    if not (rows.isdigit() and seed.isdigit()):
        raise ValueError(f"near base {text!r}: want <rows>:<seed>")
    return int(rows), int(seed)


def _cuts_cover(ends: np.ndarray, n: int) -> bool:
    """Whether the exclusive chunk ends ``ends`` cut a payload of ``n``
    bytes into non-empty chunks that cover it: first > 0, strictly
    increasing, last == n; no cuts only for no payload."""
    if len(ends) == 0 or n == 0:
        return len(ends) == 0 and n == 0
    return bool(ends[0] > 0 and ends[-1] == n
                and (ends[1:] > ends[:-1]).all())


def _parse_session(token: str) -> int:
    try:
        return int(token)
    except ValueError:
        return -1


class DedupSidecar:
    """Unix-socket dedup service around a :class:`DedupEngine`.

    One engine (and one TPU context) serves every daemon connection, one
    thread each.  ``engine.fingerprint`` runs outside ``_lock`` (it
    touches no index state), so concurrent uploads overlap on the device;
    ``_lock`` serializes only sessions, indexes and ``stats``.  Batching
    happens inside the engine's bucketed jit calls, not across requests.
    """

    def __init__(self, socket_path: str, state_dir: str | None = None,
                 config: DedupConfig | None = None) -> None:
        self.socket_path = socket_path
        self.state_dir = state_dir
        self.engine = DedupEngine(config)
        self.files: dict[str, str] = {}       # whole-file sha1 -> file id
        self.by_file: dict[str, str] = {}     # file id -> sha1
        self._sessions: dict[int, _Session] = {}  # session id -> pending
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._listener: socket.socket | None = None
        # engine_us is time inside engine.fingerprint, which runs outside
        # _lock; lock_wait_us is the wait for _lock afterwards, to append
        # the reply and the session's bookkeeping.  Both, span_us / span_n
        # and host_stall_us are folded from each request's own accumulator
        # (dedup/spans.py) under that hold of _lock.
        # verify_host_fallbacks counts DEDUP_VERIFY batches the device
        # path failed and hashlib answered (see _verify).  Read via the
        # `stats` commit subcommand.
        self.stats = {"fingerprint_bytes": 0, "chunks": 0, "requests": 0,
                      "lock_wait_us": 0, "engine_us": 0,
                      "verify_host_fallbacks": 0,
                      "span_us": {}, "span_n": {}, "host_stall_us": 0,
                      "recv_calls": 0, "recv_bytes": 0,
                      "reindex_bytes": 0, "reindex_requests": 0,
                      "ec_encode_requests": 0, "ec_encode_bytes": 0,
                      "ec_encode_us": 0}
        if state_dir:
            self._load_state()

    # -- state -------------------------------------------------------------

    def _state_paths(self) -> tuple[str, str, str]:
        d = self.state_dir or "."
        return (os.path.join(d, "sidecar_files.json"),
                os.path.join(d, "sidecar_exact.npz"),
                os.path.join(d, "sidecar_near.npz"))

    def _load_state(self) -> None:
        from fastdfs_tpu.ops.gear_cdc import CDC_SPEC_VERSION

        files_p, exact_p, near_p = self._state_paths()
        if os.path.exists(files_p):
            with open(files_p) as fh:
                blob = json.load(fh)
            # Current format: {"cdc_spec": N, "cdc_policy": P,
            # "files": {...}}; round-4 snapshots were the flat files dict
            # (spec 1 implicitly); pre-round-13 ones carry no policy
            # field (policy 1 implicitly).
            if isinstance(blob, dict) and "files" in blob:
                spec = int(blob.get("cdc_spec", 1))
                policy = int(blob.get("cdc_policy", 1))
                # no widths record: written before they were a setting,
                # so at the shipped ones
                widths = tuple(blob.get("cdc_widths", _SHIPPED_WIDTHS))
                files = blob["files"]
            else:
                spec, policy, widths, files = 1, 1, _SHIPPED_WIDTHS, blob
            if spec != CDC_SPEC_VERSION:
                # Stale chunker spec: the same bytes now chunk at
                # different offsets, so every stored chunk digest would
                # miss — discard ALL dedup state (cold restart; recipes
                # and reads are unaffected) instead of silently serving
                # a dead index.
                print(f"dedup sidecar: discarding snapshot built with "
                      f"chunker spec v{spec} (current v{CDC_SPEC_VERSION})",
                      flush=True)
                return
            if policy != self.engine.config.cdc_policy:
                # Same rule for the cut-selection policy: default and
                # skip-min cuts are different content-address namespaces,
                # so an index built under one is dead weight (and silent
                # ~0% dedup) under the other.
                print(f"dedup sidecar: discarding snapshot built with "
                      f"cdc_policy {policy} (engine runs policy "
                      f"{self.engine.config.cdc_policy})", flush=True)
                return
            if widths != self._widths():
                # And for the chunk widths: chunks cut at other widths
                # share no digest with what this engine will see.
                print(f"dedup sidecar: discarding snapshot built at chunk "
                      f"widths {':'.join(map(str, widths))} (engine runs "
                      f"{self._widths_text()})", flush=True)
                return
            self.files = files
            self.by_file = {v: k for k, v in self.files.items()}
        elif os.path.exists(exact_p) or os.path.exists(near_p):
            # Index snapshots without the files/spec record: unknown
            # chunker spec — same discard rule.
            print("dedup sidecar: discarding index snapshots with no "
                  "chunker-spec record", flush=True)
            return
        if os.path.exists(exact_p) and os.path.exists(near_p):
            try:
                self.engine = DedupEngine.load(exact_p, near_p,
                                               self.engine.config)
            except Exception as e:
                # A stale-spec, truncated, or otherwise unreadable
                # snapshot must not brick the sidecar (which would
                # fail-open EVERY upload to flat storage): keep whatever
                # exact state loads, restart the near index.
                print(f"dedup sidecar: dropping near-dup snapshot ({e}); "
                      "exact dedup state retained", flush=True)
                from fastdfs_tpu.dedup.index import ExactDigestIndex
                fresh = DedupEngine(self.engine.config)
                try:
                    fresh.exact = ExactDigestIndex.load(exact_p)
                except Exception:
                    pass
                self.engine = fresh

    def save_state(self) -> None:
        from fastdfs_tpu.ops.gear_cdc import CDC_SPEC_VERSION

        if not self.state_dir:
            return
        files_p, exact_p, near_p = self._state_paths()
        with self._lock:
            tmp = files_p + ".tmp"
            with open(tmp, "w") as fh:
                json.dump({"cdc_spec": CDC_SPEC_VERSION,
                           "cdc_policy": self.engine.config.cdc_policy,
                           "cdc_widths": list(self._widths()),
                           "files": self.files}, fh)
            os.replace(tmp, files_p)
            self.engine.save(exact_p, near_p)

    def _widths(self) -> tuple[int, int, int]:
        cfg = self.engine.config
        return cfg.min_size, cfg.avg_bits, cfg.max_size

    def _widths_text(self) -> str:
        return ":".join(map(str, self._widths()))

    def device_info(self) -> dict:
        """What this process actually runs on, as JAX and the engine
        report it — part of the ``stats`` reply."""
        import jax

        devs = jax.local_devices()
        return {"backend": jax.default_backend(),
                "device_kind": devs[0].device_kind,
                "device_count": len(devs),
                "use_pallas": self.engine.use_pallas,
                "fan_out": self.engine.fan_out,
                "memory_peak_bytes": max(
                    int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
                    for dev in devs),
                # dict(): one atomic copy; connection threads add to it
                "device_bytes": {str(dev): n for dev, n in sorted(
                    dict(self.engine.device_bytes).items())},
                "tiles_by_rows": {str(rows): n for rows, n in sorted(
                    dict(self.engine.tiles_by_rows).items())},
                "widths": dict(zip(("min_size", "avg_bits", "max_size"),
                                   self._widths())),
                **self.engine.launched}

    # -- request handlers --------------------------------------------------

    def _fingerprint(self, body, with_cuts: bool = False,
                     acc: dict | None = None) -> tuple[int, bytes]:
        """``body`` is any bytes-like object; ``_serve_conn`` hands over a
        view of its receive buffer, and the payload stays a view of it all
        the way into the engine's ``np.frombuffer``.  Nothing that outlives
        this call may refer to it: the buffer is the next request's."""
        if acc is None:
            acc = new_acc()
        with span("fdfs.sidecar.parse", acc, True):
            body = memoryview(body)
            if len(body) < 16:
                return 22, b""
            session_id, base_offset = _I64X2.unpack_from(body)
            cuts = None
            if with_cuts:
                # DEDUP_FINGERPRINT_CUTS: the daemon already ran the
                # (identical) native CDC; body carries the cut offsets.
                if len(body) < 24:
                    return 22, b""
                n_cuts = _I64.unpack_from(body, 16)[0]
                if n_cuts < 0 or 24 + 8 * n_cuts > len(body):
                    return 22, b""
                ends = np.frombuffer(body, dtype=">i8", count=n_cuts,
                                     offset=24)
                data = body[24 + 8 * n_cuts:]
                # Cuts must exactly cover the payload: an empty cut list
                # with data would "succeed" with zero chunks and a recipe
                # covering none of the bytes.
                if not _cuts_cover(ends, len(data)):
                    return 22, b""
                # A caller that cut at other widths (one that never said
                # `widths`: every daemon of this tree does) shows here at
                # the latest: no chunk of this engine's is over max_size.
                if n_cuts and int(np.diff(ends, prepend=0).max()) > \
                        self.engine.config.max_size:
                    print("dedup sidecar: REFUSING cuts over max_size "
                          f"{self.engine.config.max_size}: the caller "
                          "chunks at other widths than this engine "
                          f"({self._widths_text()})", flush=True)
                    return 22, b""
                cuts = ends.tolist()
            else:
                data = body[16:]
        # Pure compute OUTSIDE the lock: engine.fingerprint touches no
        # index state (its docstring is the contract), and JAX dispatch
        # is thread-safe — so concurrent daemon uploads overlap their
        # device round-trips instead of queueing behind one global lock.
        # Only session/stats/index mutation is serialized.
        spans, digests, sigs = self.engine.fingerprint(data, cuts=cuts,
                                                       acc=acc)
        with span("fdfs.sidecar.lock_wait", acc):
            self._lock.acquire()
        try:
            with span("fdfs.sidecar.reply", acc, True):
                sess = self._sessions.setdefault(session_id, _Session())
                sess.touched = time.monotonic()
                rec = np.empty(len(spans), dtype=_CHUNK_REC)
                if len(spans):
                    raw = np.asarray(digests, dtype=">u4").tobytes()
                    ol = np.asarray(spans, dtype=np.int64)
                    rec["off"] = ol[:, 0] + base_offset
                    rec["len"] = ol[:, 1]
                    rec["dig"] = np.frombuffer(raw, dtype="V20")
                    # Digest attribution (which file first carried a
                    # chunk, for near-dup reporting) stays buffered in the
                    # session until commit binds the real file id — the
                    # index never sees provisional entries.
                    sess.digests.append((raw, rec["off"].astype(np.int64)))
                    sig = np.asarray(sigs).min(axis=0)
                    sess.sig = (sig if sess.sig is None
                                else np.minimum(sess.sig, sig))
                self.stats["fingerprint_bytes"] += len(data)
                self.stats["chunks"] += len(spans)
                if session_id & REINDEX_SESSION_BIT:
                    self.stats["reindex_bytes"] += len(data)
                    self.stats["reindex_requests"] += 1
            # The two old counters are those two spans: one clock for each.
            ns = acc["span_ns"]
            self.stats["engine_us"] += ns["fdfs.engine.fingerprint"] // 1000
            self.stats["lock_wait_us"] += ns["fdfs.sidecar.lock_wait"] // 1000
            self.stats["host_stall_us"] += (
                acc["host_wall_ns"] - acc["host_cpu_ns"]) // 1000
            self._fold(acc)
        finally:
            self._lock.release()
        # The request's size, on the trace's clock.  host_wall_us and
        # host_cpu_us are summed over parse, pack, scatter and reply:
        # their difference is time this thread had no interpreter or core.
        mark("fdfs.sidecar.request_done", bytes=len(data),
             host_wall_us=acc["host_wall_ns"] // 1000,
             host_cpu_us=acc["host_cpu_ns"] // 1000)
        return 0, _I64.pack(len(spans)) + rec.tobytes()

    def _fold(self, acc: dict) -> None:
        """The spans ``acc`` has closed so far into ``stats``, and out of
        ``acc``.  A fingerprint request calls it under the ``_lock`` it
        holds for its reply; what closes after that (``request``, ``send``,
        and every span of the other opcodes) is added by ``_serve_conn``
        without the lock, as ``requests`` is counted."""
        us, n = self.stats["span_us"], self.stats["span_n"]
        for name, ns in acc["span_ns"].items():
            us[name] = us.get(name, 0) + ns // 1000
            n[name] = n.get(name, 0) + acc["span_n"][name]
        acc["span_ns"].clear()
        acc["span_n"].clear()
        # a fingerprint body's receive, as _serve_conn counted it
        self.stats["recv_calls"] += acc.pop("recv_calls", 0)
        self.stats["recv_bytes"] += acc.pop("recv_bytes", 0)

    def _query(self, body: bytes) -> tuple[int, bytes]:
        sha1_hex = body.decode("ascii", "replace").strip()
        with self._lock:
            fid = self.files.get(sha1_hex)
        return 0, fid.encode() if fid else b""

    def _commit(self, body: bytes, acc: dict | None = None
                ) -> tuple[int, bytes]:
        text = body.decode("utf-8", "replace")
        parts = text.split()
        if not parts:
            return 22, b""
        if parts[0] == "trace":     # the directory may hold blanks
            return self._trace(text.strip().split(None, 2)[1:])
        if parts[0] == "widths" and len(parts) == 4:
            if tuple(_parse_session(p) for p in parts[1:]) == self._widths():
                return 0, b""
            why = (f"the daemon chunks at {':'.join(parts[1:])}, this "
                   f"engine at {self._widths_text()}")
            print(f"dedup sidecar: REFUSING a daemon: {why}; nothing is "
                  "fingerprinted for it (set storage.conf dedup_cdc_widths "
                  "and --cdc-widths alike)", flush=True)
            return 22, why.encode()
        # The near-dup index has a lock of its own (a write is a dispatch
        # to the device): its row is written, and a tombstone set, after
        # _lock is let go and before the daemon is answered, so every
        # query sent after the acknowledgement sees it.
        if parts[0] == "commitchunks" and len(parts) == 3:
            if acc is None:
                acc = new_acc()
            with self._lock, span("fdfs.sidecar.commit", acc) as s:
                sess = self._sessions.pop(_parse_session(parts[1]), None)
                if sess is not None and sess.digests:
                    # the session's digests as one batch
                    new = self.engine.exact.insert_batch(
                        b"".join(raw for raw, _ in sess.digests), parts[2],
                        np.concatenate([off for _, off in sess.digests]),
                        acc=acc)
                    s.note(digests=sum(len(off) for _, off in sess.digests),
                           new=new)
            if sess is not None and sess.sig is not None:
                self._near_update(self.engine.near.add, sess.sig, parts[2],
                                  acc=acc)
            return 0, b""
        if parts[0] == "forget" and len(parts) == 2:
            with self._lock:
                sha1 = self.by_file.pop(parts[1], None)
                if sha1 is not None and self.files.get(sha1) == parts[1]:
                    del self.files[sha1]
                # Exact attributions for the deleted file leave the index
                # too (they would otherwise accumulate in RAM + snapshots
                # forever).  The daemon's ChunkStore owns true chunk
                # refcounts; this index only answers "who first carried
                # it", so dropping the tombstoned carrier is safe — a
                # later upload of the same chunk re-attributes it.  One
                # vectorized pass over the index's carrier column — no
                # per-file digest-list side table in RAM.
                self.engine.exact.remove_by_carrier(parts[1])
            self._near_update(self.engine.near.remove, parts[1])
            return 0, b""
        with self._lock:
            if parts[0] == "commitfile" and len(parts) == 3:
                self.files.setdefault(parts[1], parts[2])
                self.by_file[parts[2]] = parts[1]
                return 0, b""
            if parts[0] == "stats" and len(parts) == 1:
                return 0, json.dumps({**self.stats,
                                      **self.engine.exact.stats(),
                                      **self.engine.near.stats(),
                                      **self.device_info()}).encode()
            if parts[0] == "abort" and len(parts) == 2:
                self._sessions.pop(_parse_session(parts[1]), None)
                return 0, b""
        return 22, b""

    @staticmethod
    def _near_update(update, *args, **kw) -> None:
        """``near.add`` / ``near.remove`` for a commit or a forget.  The
        file is stored and exactly deduplicated whatever happens here (a
        device too full to grow the index on): it is only not found by
        ``near_dups``, and the log says so."""
        try:
            update(*args, **kw)
        except Exception as e:  # noqa: BLE001 — the commit still answers
            print(f"dedup sidecar: near-dup index not updated for "
                  f"{args[-1]} ({type(e).__name__}: {e})", flush=True)

    @staticmethod
    def _trace(args: list[str]) -> tuple[int, bytes]:
        """``trace start <dir>`` / ``trace stop``: a JAX profiler trace of
        this process, with the options the benchmark's launcher uses
        (TraceMe events, which the ``fdfs.*`` spans are; no Python
        tracer).  Never under ``_lock``: starting takes seconds."""
        import jax

        try:
            if args[:1] == ["start"] and len(args) == 2:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(args[1], profiler_options=opts)
                return 0, b""
            if args == ["stop"]:
                jax.profiler.stop_trace()
                return 0, b""
        except RuntimeError as e:   # one runs already, or none does
            return 16, str(e).encode()
        return 22, b""

    def _verify(self, body: bytes) -> tuple[int, bytes]:
        """DEDUP_VERIFY (136): batched chunk-integrity check for the
        storage scrubber.  Body = 8B count + count x (8B length + 20B
        expected raw SHA1) + payloads concatenated; response = count
        bytes (0 = match, 1 = mismatch).

        Pure compute — no index or session state — so it runs entirely
        outside the engine lock, on the accelerator via
        ``ops/sha1.sha1_batch`` (one padded (N, L) batch per request)
        with a hashlib fallback if the device path fails for any
        reason: a verify answer must never be wrong, only slower.  Each
        fallback is counted (``verify_host_fallbacks``) so a device that
        never answers does not pass for one that does.
        """
        if len(body) < 8:
            return 22, b""
        count = _I64.unpack_from(body)[0]
        if count < 0 or 8 + count * 28 > len(body):
            return 22, b""
        lengths = []
        digests = []
        for i in range(count):
            off = 8 + i * 28
            ln = _I64.unpack_from(body, off)[0]
            if ln < 0:
                return 22, b""
            lengths.append(ln)
            digests.append(body[off + 8:off + 28])
        payloads = body[8 + count * 28:]
        if sum(lengths) != len(payloads):
            return 22, b""
        chunks = []
        off = 0
        for ln in lengths:
            chunks.append(payloads[off:off + ln])
            off += ln
        got: list[bytes] = []
        try:
            got = self._batch_sha1(chunks)
        except Exception as e:  # noqa: BLE001 — fall back to the host
            print(f"dedup sidecar: batched verify fell back to hashlib "
                  f"({type(e).__name__}: {e})", flush=True)
        if len(got) != count:
            import hashlib
            with self._lock:
                self.stats["verify_host_fallbacks"] += 1
            got = [hashlib.sha1(c).digest() for c in chunks]
        mask = bytes(0 if g == d else 1 for g, d in zip(got, digests))
        return 0, mask

    @staticmethod
    def _batch_sha1(chunks: list[bytes]) -> list[bytes]:
        """One sha1_batch dispatch over zero-padded rows (device path)."""
        if not chunks:
            return []
        from fastdfs_tpu.ops.sha1 import digest_bytes, sha1_batch
        max_len = max(len(c) for c in chunks)
        batch = np.zeros((len(chunks), max(max_len, 1)), dtype=np.uint8)
        lens = np.zeros((len(chunks),), dtype=np.int32)
        for i, c in enumerate(chunks):
            batch[i, :len(c)] = np.frombuffer(c, dtype=np.uint8)
            lens[i] = len(c)
        raw = digest_bytes(sha1_batch(batch, lens))
        return [raw[i * 20:(i + 1) * 20] for i in range(len(chunks))]

    def _neardups(self, body: bytes, acc: dict | None = None
                  ) -> tuple[int, bytes]:
        """Ranked near-dup report for a stored file id (the production
        query surface for the near-dup index; without it the index is
        write-only).  Status 61 (ENODATA) when the file is unknown to the
        near index — flat, whole-file-deduped, or never committed.  Never
        under ``_lock``: the query waits for the index's next pass."""
        file_id = body.decode("utf-8", "replace").strip()
        if not file_id:
            return 22, b""
        sig = self.engine.near.signature_of(file_id)
        if sig is None:
            return 61, b""
        cfg = self.engine.config
        pairs = self.engine.near.query(
            sig, top_k=cfg.near_dup_top_k * 2 + 1,
            min_similarity=cfg.near_dup_threshold, acc=acc)
        lines = [f"{ref} {score:.4f}" for ref, score in pairs
                 if ref != file_id][:cfg.near_dup_top_k * 2]
        return 0, "\n".join(lines).encode()

    def _ec_encode(self, body, acc: dict) -> tuple[int, bytes]:
        """DEDUP_EC_ENCODE (150): the m parity shards of k data shards.
        ``body`` is a view of the receive buffer; the shards reach the
        engine as a view of it too, and nothing here outlives the call."""
        body = memoryview(body)
        if len(body) < _I64X3.size:
            return 22, b""
        k, m, shard_len = _I64X3.unpack_from(body)
        if not (0 < k and 0 < m and k + m <= 255 and shard_len > 0
                and len(body) == _I64X3.size + k * shard_len):
            return 22, b""
        data = np.frombuffer(body, dtype=np.uint8, count=k * shard_len,
                             offset=_I64X3.size).reshape(k, shard_len)
        with span("fdfs.ec.encode", acc, k=k, m=m, shard_len=shard_len,
                  bytes=k * shard_len):
            parity = self.engine.ec_encode(data, m)
        with self._lock:
            self.stats["ec_encode_requests"] += 1
            self.stats["ec_encode_bytes"] += k * shard_len
            self.stats["ec_encode_us"] += acc["span_ns"]["fdfs.ec.encode"] // 1000
        return 0, parity

    def _reap_stale_sessions(self) -> None:
        cutoff = time.monotonic() - _SESSION_TTL
        with self._lock:
            stale = [s for s, sess in self._sessions.items()
                     if sess.touched < cutoff]
            for s in stale:
                del self._sessions[s]
        if stale:
            print(f"dedup sidecar: reaped {len(stale)} stale sessions",
                  flush=True)

    # -- server loop -------------------------------------------------------

    def _handle(self, cmd: int, body: bytes, acc: dict) -> tuple[int, bytes]:
        """One request, handler entry to reply built: the root span, which
        carries the identifiers the daemon minted for the upload."""
        ids = {}
        if cmd in _FINGERPRINT_CMDS and len(body) >= 16:
            ids = dict(zip(("session", "base_offset"),
                           _I64X2.unpack_from(body)))
            ids["reindex"] = int(bool(ids["session"] & REINDEX_SESSION_BIT))
        with span("fdfs.sidecar.request", acc, cmd=cmd, bytes=len(body),
                  **ids):
            if cmd == StorageCmd.DEDUP_FINGERPRINT:
                return self._fingerprint(body, acc=acc)
            if cmd == StorageCmd.DEDUP_FINGERPRINT_CUTS:
                return self._fingerprint(body, with_cuts=True, acc=acc)
            if cmd == StorageCmd.DEDUP_QUERY:
                return self._query(body)
            if cmd == StorageCmd.DEDUP_COMMIT:
                return self._commit(body, acc)
            if cmd == StorageCmd.DEDUP_NEARDUPS:
                return self._neardups(body, acc)
            if cmd == StorageCmd.DEDUP_EC_ENCODE:
                return self._ec_encode(body, acc)
            if cmd == StorageCmd.DEDUP_VERIFY:
                # the scrubber's batch: background work on the same chip
                with span("fdfs.sidecar.verify", acc):
                    return self._verify(body)
            if cmd == StorageCmd.ACTIVE_TEST:
                return 0, b""
            return 22, b""

    def _serve_conn(self, conn: socket.socket) -> None:
        # One receive buffer a connection, grown to the largest body it has
        # carried and let go with the connection: the daemon keeps as many
        # idle ones as it has dio workers (dedup.h, max_idle_fds_), so that
        # many segments (dedup_segment_bytes) can stay pinned.  A buffer per
        # request cost 1.5 ms/MB more on the v5e's host, most of it
        # unmapping 64 MB after every reply (PERF.md section 6, PR 27).
        kept = bytearray()
        try:
            while not self._stop.is_set():
                hdr = self._recv_exact(conn, HEADER_SIZE)
                if hdr is None:
                    return
                # The wait for a header on an idle pooled connection is
                # the daemon's time, not a span: recv starts here.
                acc = new_acc()
                h = unpack_header(hdr)
                if h.pkg_len < 0 or h.pkg_len > (1 << 31):
                    return
                if len(kept) < h.pkg_len:
                    kept = bytearray(h.pkg_len)
                body = memoryview(kept)[:h.pkg_len]
                with span("fdfs.sidecar.recv", acc, cmd=h.cmd,
                          bytes=h.pkg_len):
                    calls = self._recv_into(conn, body)
                if calls is None:
                    return
                self.stats["requests"] += 1
                # A fingerprint payload or a stripe goes to the engine as
                # a view of the buffer: copied once, by the kernel.  Every
                # other opcode decodes and splits a body of tens of bytes
                # (verify: a batch), as bytes.
                if h.cmd in _FINGERPRINT_CMDS:
                    acc["recv_calls"], acc["recv_bytes"] = calls, h.pkg_len
                status, resp = self._handle(
                    h.cmd, body if h.cmd in _VIEW_CMDS else bytes(body), acc)
                # The next request overwrites the buffer, so no handler
                # may keep a view of it: with this one let go, nothing
                # refers to it between requests (the tests resize it to
                # show that).
                body.release()
                with span("fdfs.sidecar.send", acc, cmd=h.cmd,
                          bytes=len(resp)):
                    conn.sendall(_pack_header(len(resp),
                                              StorageCmd.RESP, status) + resp)
                self._fold(acc)     # what closed after the handler's fold
        except OSError:
            pass
        finally:
            conn.close()

    @staticmethod
    def _recv_into(conn: socket.socket, buf: bytearray | memoryview
                   ) -> int | None:
        """Fill ``buf`` from the socket: the number of ``recv_into`` calls
        that took, or None if the peer closed first.  Each call asks for
        the whole remainder with ``MSG_WAITALL``, so on a blocking socket
        (an accepted connection is one, whatever the listener's timeout)
        the thread waits inside the kernel, without the interpreter lock,
        until the body is complete: one call a body, not one per 200 KB
        the peer's ``send`` happened to hand over.  A short return (a
        signal; a socket with a timeout, as ``rpc``'s) loops."""
        view = memoryview(buf)
        got = calls = 0
        while got < len(view):
            n = conn.recv_into(view[got:], len(view) - got,
                               socket.MSG_WAITALL)
            if n == 0:
                return None
            got += n
            calls += 1
        return calls

    @classmethod
    def _recv_exact(cls, conn: socket.socket, n: int) -> bytes | None:
        buf = bytearray(n)
        return None if cls._recv_into(conn, buf) is None else bytes(buf)

    def _housekeeping_loop(self, snapshot_interval: float) -> None:
        """Snapshot + stale-session reaping on a dedicated timer thread:
        a steadily-busy listener must not defer them (the accept-timeout
        scheduling they used to ride starves under sustained traffic,
        making crash loss unbounded instead of one snapshot interval)."""
        while not self._stop.wait(snapshot_interval):
            # Catch EVERYTHING: one bad snapshot attempt (OSError, but
            # also numpy/json errors from racing state) must not kill the
            # thread and silently disable snapshots + session reaping.
            try:
                self.save_state()
            except Exception as e:
                print(f"dedup sidecar: snapshot failed: {e}", flush=True)
            try:
                self._reap_stale_sessions()
            except Exception as e:
                print(f"dedup sidecar: session reap failed: {e}", flush=True)

    def serve_forever(self, ready_event: threading.Event | None = None,
                      snapshot_interval: float = 60.0) -> None:
        try:
            os.unlink(self.socket_path)
        except FileNotFoundError:
            pass
        self._listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._listener.bind(self.socket_path)
        self._listener.listen(16)
        self._listener.settimeout(0.5)
        if ready_event is not None:
            ready_event.set()
        housekeeper = threading.Thread(
            target=self._housekeeping_loop, args=(snapshot_interval,),
            daemon=True)
        housekeeper.start()
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            threading.Thread(target=self._serve_conn,
                             args=(conn,), daemon=True).start()
        self._stop.set()
        housekeeper.join(timeout=5.0)
        self.save_state()
        self._listener.close()
        try:
            os.unlink(self.socket_path)
        except FileNotFoundError:
            pass

    def stop(self) -> None:
        self._stop.set()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description="fastdfs_tpu dedup sidecar")
    ap.add_argument("--socket", required=True, help="unix socket path")
    ap.add_argument("--state-dir", default=None,
                    help="snapshot dir (checkpoint/resume)")
    ap.add_argument("--snapshot-interval", type=float, default=60.0)
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (cpu, for tests and for "
                         "pricing the host path on purpose).  Without it "
                         "the sidecar refuses to start on anything but a "
                         "TPU.")
    ap.add_argument("--cdc-policy", type=int, default=1, choices=(1, 2),
                    help="cut-selection policy: 1 = default (frozen, "
                         "ref-identical cuts), 2 = skip-min "
                         "(arXiv:2508.05797; different boundaries — new "
                         "groups only, see OPERATIONS.md).  Snapshots "
                         "built under another policy are discarded at "
                         "load.")
    ap.add_argument("--cdc-widths", type=parse_widths,
                    default=_SHIPPED_WIDTHS, metavar="MIN:BITS:MAX",
                    help="the chunk widths, as storage.conf's "
                         "dedup_cdc_widths states them (default 2K:13:64K). "
                         "A daemon that chunks at other widths is refused; "
                         "snapshots built at other widths are discarded at "
                         "load.")
    ap.add_argument("--fan-out", type=int, default=None,
                    help="shard each fingerprint batch's rows over this "
                         "many local devices (default: auto — all local "
                         "devices on a multi-chip TPU host, else 1)")
    ap.add_argument("--near-base", type=parse_near_base, default=None,
                    metavar="ROWS:SEED",
                    help="start the near-dup index with ROWS seeded "
                         "signatures made on the device (refs base/<row>): "
                         "the node at the size it will hold, before it "
                         "holds it (OPERATIONS.md, Device memory).  A "
                         "snapshot written over another base is refused at "
                         "load.")
    args = ap.parse_args(argv)

    import jax

    from fastdfs_tpu import compile_cache

    cache_dir = compile_cache.configure()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
        print(f"dedup sidecar: platform forced to {args.platform!r} "
              "(--platform)", flush=True)
    backend = jax.default_backend()  # a backend that cannot start raises
    if not args.platform and backend != "tpu":
        # The engine would hash on the host with hashlib and the daemon
        # would never know: no error, no chip.  Refuse instead.
        print(f"dedup sidecar: no TPU: jax.default_backend() is "
              f"{backend!r}.  This service exists to run the fingerprint "
              "kernels on the chip; pass --platform cpu to run the host "
              "path on purpose.", file=sys.stderr, flush=True)
        return 2
    print(f"dedup sidecar: backend {backend}, "
          f"{len(jax.local_devices())} x "
          f"{jax.local_devices()[0].device_kind}, compile cache "
          f"{cache_dir}", flush=True)

    min_size, avg_bits, max_size = args.cdc_widths
    config = DedupConfig(min_size=min_size, avg_bits=avg_bits,
                         max_size=max_size, cdc_policy=args.cdc_policy,
                         fan_out=args.fan_out, near_base=args.near_base)
    sidecar = DedupSidecar(args.socket, state_dir=args.state_dir,
                           config=config)
    signal.signal(signal.SIGTERM, lambda *_: sidecar.stop())
    signal.signal(signal.SIGINT, lambda *_: sidecar.stop())
    t0 = time.monotonic()
    sidecar.engine.warmup()  # compile all shapes BEFORE accepting traffic
    near = sidecar.engine.near.stats()
    print(f"dedup sidecar warmed in {time.monotonic() - t0:.1f}s at chunk "
          f"widths {sidecar._widths_text()}, near-dup index of "
          f"{near['near_rows']} rows ({near['near_base_rows']} base) in "
          f"{near['near_resident_bytes'] / 1e6:.0f} MB on the device, "
          f"listening on {args.socket}", flush=True)
    sidecar.serve_forever(snapshot_interval=args.snapshot_interval)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
