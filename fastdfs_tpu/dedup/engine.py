"""DedupEngine: the upload-path fingerprint pipeline.

Pipeline per ingested byte stream (north star; replaces the scalar CRC32
loop in the reference's ``storage/storage_dio.c:dio_write_file()``):

    bytes ──CDC (gear, position-parallel)──► chunk spans
          ──pad to pow2 buckets──► fixed-shape batches (XLA-friendly)
          ──SHA1 batch + MinHash batch (one jit per bucket shape)──►
          digests + signatures
          ──exact index──► per-chunk write/skip verdicts
          ──LSH index──► file-level near-duplicate candidates

Chunks are padded to power-of-two length buckets so every distinct jitted
shape is reused across files (XLA traces once per bucket, not per file).
The file-level MinHash signature is the element-wise min over its chunks'
signatures — exact for the union of their shingle sets (min of mins), so
near-dup detection works at file granularity without rehashing the file.
"""

from __future__ import annotations

import functools
import hashlib
import threading
from dataclasses import dataclass, field

import numpy as np

from fastdfs_tpu.dedup.index import ExactDigestIndex, MinHashLSHIndex
from fastdfs_tpu.dedup.spans import new_acc, span
from fastdfs_tpu.ops import gear_cdc
from fastdfs_tpu.ops.minhash import DEFAULT_PERMS, DEFAULT_SHINGLE, minhash_batch
from fastdfs_tpu.ops.sha1 import digest_bytes


def _tpu_available() -> bool:
    """True when JAX's default backend is a TPU.  A backend that fails to
    initialise raises here: answering False would send every batch down
    the hashlib host path while the caller believes the chip is up."""
    import jax
    return jax.default_backend() == "tpu"


@dataclass(frozen=True)
class DedupConfig:
    min_size: int = gear_cdc.DEFAULT_MIN_SIZE
    avg_bits: int = gear_cdc.DEFAULT_AVG_BITS
    max_size: int = gear_cdc.DEFAULT_MAX_SIZE
    num_perms: int = DEFAULT_PERMS
    shingle: int = DEFAULT_SHINGLE
    lsh_bands: int = 16
    near_dup_threshold: float = 0.5
    near_dup_top_k: int = 5
    # Fixed row tile per jitted batch: chunks are processed in groups of
    # exactly this many rows (last group padded), so each pow2 length
    # bucket compiles exactly ONE XLA shape — a varying chunk count would
    # otherwise retrace per distinct N and dominate wall-clock.
    row_tile: int = 256
    # None = auto: Pallas kernels on TPU, XLA reference elsewhere.  The
    # two paths are bit-identical (tests/test_pallas_kernels.py).
    use_pallas: bool | None = None
    # Cut-selection policy: CDC_POLICY_DEFAULT (frozen, ref-identical) or
    # the opt-in CDC_POLICY_SKIPMIN.  NEVER change on a live index — the
    # policies are distinct content-address namespaces (the sidecar
    # discards snapshots on mismatch, same as a spec bump).
    cdc_policy: int = gear_cdc.CDC_POLICY_DEFAULT
    # Fingerprint fan-out: shard each (row_tile, blen) batch's rows over
    # this many local devices via parallel.make_fingerprint_step.
    # None = auto (all local devices when >1 and a TPU backend is up;
    # otherwise 1); 1 = single-device paths.  row_tile must divide by it.
    fan_out: int | None = None


@dataclass
class ChunkRecord:
    offset: int
    length: int
    digest: bytes          # 20-byte SHA1
    duplicate: bool
    dup_of: object = None  # ref stored at first sight of this digest


@dataclass
class IngestReport:
    file_ref: str
    size: int
    chunks: list[ChunkRecord] = field(default_factory=list)
    file_signature: np.ndarray | None = None
    near_dups: list[tuple[object, float]] = field(default_factory=list)

    @property
    def bytes_total(self) -> int:
        return self.size

    @property
    def bytes_duplicate(self) -> int:
        return sum(c.length for c in self.chunks if c.duplicate)

    @property
    def dedup_ratio(self) -> float:
        return self.bytes_duplicate / self.size if self.size else 0.0


def _bucket_len(n: int, min_size: int, max_size: int) -> int:
    """Smallest power-of-two >= n, clamped to [min_size, max_size]."""
    b = max(min_size, 1)
    while b < n:
        b <<= 1
    return min(b, max_size) if n <= max_size else n


@functools.lru_cache(maxsize=64)
def _packed_concat(half: int):
    """Jitted (digests..., sigs...) -> one (T, 5+P) array, cached per
    tile count (segment sizes repeat, so arities do too)."""
    import jax
    import jax.numpy as jnp

    # The name is what the device trace's modules line shows
    # (jit_fdfs_packed_concat), whatever the tile count.
    def fdfs_packed_concat(*args):
        return jnp.concatenate(
            [jnp.concatenate([args[i], args[half + i]], axis=1)
             for i in range(half)])
    return jax.jit(fdfs_packed_concat)


class DedupEngine:
    """Stateful dedup engine: chunk, fingerprint, and judge byte streams.

    One engine per storage process.  Compute (CDC/SHA1/MinHash) runs on the
    accelerator; index mutation stays on the host.  The verdicts gate disk
    writes in the storage daemon (write unique chunks, reference dups).
    """

    def __init__(self, config: DedupConfig | None = None) -> None:
        self.config = config or DedupConfig()
        if self.config.cdc_policy not in (gear_cdc.CDC_POLICY_DEFAULT,
                                          gear_cdc.CDC_POLICY_SKIPMIN):
            raise ValueError(f"unknown cdc_policy {self.config.cdc_policy}")
        self.exact = ExactDigestIndex()
        self.near = MinHashLSHIndex(self.config.num_perms, self.config.lsh_bands)
        use_pallas = self.config.use_pallas
        if use_pallas is None:
            # The survivor kernel is specialized to the default shingle
            # width; other widths take the (bit-identical) XLA reference.
            use_pallas = _tpu_available() and self.config.shingle == 5
        fan = self.config.fan_out
        if fan is None:
            # Auto fan-out only where it pays: a multi-chip TPU host.  On
            # CPU hosts the XLA sha1 compile cost per bucket shape (~2 min
            # each) dwarfs any parallel win, so auto stays single-path —
            # tests opt in explicitly with tiny geometries.
            if use_pallas:
                import jax
                fan = len(jax.local_devices())
            else:
                fan = 1
        if fan > 1 and self.config.row_tile % fan:
            raise ValueError(f"row_tile {self.config.row_tile} must divide "
                             f"by fan_out {fan}")
        # Resolved from the config's None = auto; the sidecar's `stats`
        # reply reports both.  The fan-out step runs the XLA reference
        # kernels under shard_map, so Pallas means the one-device path.
        self.fan_out = fan
        self.use_pallas = use_pallas and fan == 1
        self._fp_step = None  # built lazily: jitted multi-device step
        # Batch bytes by the device whose rows they were, read off the
        # result arrays' own shards: {device id: bytes}.  fingerprint()
        # runs on many connection threads at once, hence the lock.
        self.device_bytes: dict[int, int] = {}
        self._placed_lock = threading.Lock()

    def _count_placed(self, result, row_bytes: int) -> None:
        with self._placed_lock:
            for shard in result.addressable_shards:
                dev = shard.device.id
                self.device_bytes[dev] = (self.device_bytes.get(dev, 0)
                                          + shard.data.shape[0] * row_bytes)

    def _fingerprint_batch(self, batch: np.ndarray, lens: np.ndarray):
        """Dispatch one (row_tile, blen) batch; returns device arrays
        (futures) so callers can overlap multiple buckets in flight."""
        cfg = self.config
        if self.fan_out > 1:
            # Multi-chip fan-out: rows shard over every local device via
            # ONE jitted shard_map (parallel.make_fingerprint_step) —
            # bit-identical digests/signatures to the single-device
            # paths (tests/test_cdc_kernels.py pins this).
            if self._fp_step is None:
                from fastdfs_tpu.parallel.ingest_step import (
                    fingerprint_mesh, make_fingerprint_step)
                self._fp_step = make_fingerprint_step(
                    fingerprint_mesh(self.fan_out),
                    cfg.num_perms, cfg.shingle)
            # jit owns the transfer here: it splits the rows across the
            # mesh per in_specs, so a manual single-device device_put
            # would only add a copy.
            d, s = self._fp_step(batch, lens.astype(np.int32))
            self._count_placed(d, batch.shape[1])
            return d, s
        if self.use_pallas:
            import jax

            from fastdfs_tpu.ops.pallas_minhash import minhash_batch_pallas
            from fastdfs_tpu.ops.pallas_sha1 import sha1_batch_pallas
            # ONE explicit transfer shared by both kernels: passing the
            # numpy batch to each jit would transfer it once per kernel.
            batch = jax.device_put(batch)
            lens = jax.device_put(lens)
            sub = max(1, min(16, batch.shape[0] // 128))
            d = sha1_batch_pallas(batch, lens, int(batch.shape[1]), sub=sub)
            s = minhash_batch_pallas(batch, lens, cfg.num_perms, cfg.shingle)
            self._count_placed(d, batch.shape[1])
        else:
            # Host path: hashlib per row.  The XLA sha1_batch exists as the
            # jittable reference (tests/test_sha1.py) but its 80-round
            # unrolled graph costs ~2 minutes of XLA-CPU compile per bucket
            # shape, while hashlib runs at ~1 GB/s with none — off the TPU
            # the scalar loop IS the right tool.
            d = np.zeros((batch.shape[0], 5), dtype=np.uint32)
            for i in range(batch.shape[0]):
                dig = hashlib.sha1(batch[i, :lens[i]].tobytes()).digest()
                d[i] = np.frombuffer(dig, dtype=">u4")
            s = minhash_batch(batch, lens, cfg.num_perms, cfg.shingle)
        return d, s

    # -- pure compute ------------------------------------------------------

    def fingerprint(self, data: bytes, cuts: list[int] | None = None,
                    acc: dict | None = None
                    ) -> tuple[list[tuple[int, int]], np.ndarray, np.ndarray]:
        """Chunk + fingerprint a stream: returns (spans, digests, signatures).

        spans: list of (offset, length).  digests: (N, 5) uint32.
        signatures: (N, P) uint32.  No index state is touched.

        ``cuts`` (exclusive chunk ends) skips the chunking pass when the
        caller already ran an identical CDC — the daemon's native AVX2
        chunker shares the gear table, so in sidecar mode the bytes only
        cross the accelerator link once, for hashing.

        ``acc`` (``spans.new_acc()``) takes the call's stage times; the
        stages are ``fdfs.engine.*`` spans of a running profiler trace,
        one per tile at the most.
        """
        if acc is None:
            acc = new_acc()
        with span("fdfs.engine.fingerprint", acc):
            return self._fingerprint(data, cuts, acc)

    def _fingerprint(self, data, cuts, acc: dict):
        cfg = self.config
        if cuts is None:
            cuts = gear_cdc.chunk_stream(data, cfg.min_size, cfg.avg_bits,
                                         cfg.max_size,
                                         cdc_policy=cfg.cdc_policy)
        spans: list[tuple[int, int]] = []
        last = 0
        for c in cuts:
            spans.append((last, c - last))
            last = c
        if not spans:
            return [], np.zeros((0, 5), np.uint32), np.zeros((0, cfg.num_perms), np.uint32)

        digests = np.zeros((len(spans), 5), dtype=np.uint32)
        sigs = np.zeros((len(spans), cfg.num_perms), dtype=np.uint32)
        arr = np.frombuffer(data, dtype=np.uint8)

        # Group chunks by pow2 bucket so each jitted shape is reused.
        by_bucket: dict[int, list[int]] = {}
        for i, (off, ln) in enumerate(spans):
            by_bucket.setdefault(_bucket_len(ln, cfg.min_size, cfg.max_size), []).append(i)

        # Fixed (row_tile, blen) shapes: one compile per bucket, ever.
        # Transfer discipline (every device<->host transfer pays a fixed
        # latency; what each costs on the v5e's PCIe link is not measured
        # yet, see PERF.md):
        #   * tiles are packed into REUSED thread-local staging buffers,
        #   * all tiles dispatch asynchronously,
        #   * digests and signatures are concatenated ON DEVICE so the
        #     whole segment costs exactly one fetch.
        # Device memory stays bounded by the segment size the daemon
        # streams (storage.conf:dedup_segment_bytes), not the file size.
        import jax
        import jax.numpy as jnp

        tile = cfg.row_tile
        groups: list[list[int]] = []
        outs_d = []
        outs_s = []
        # Double-buffered staging (ADVICE r5): tiles dispatch
        # asynchronously and are fetched only once at the end, and PJRT
        # host-buffer semantics are backend-dependent — some clients
        # hold the host buffer zero-copy until the transfer completes.
        # Rotate 2 staging slots per bucket size AND block on the tile
        # that last used a slot before reusing it (its outputs being
        # ready implies its input transfer finished) — rotation alone
        # would still overwrite tile N while in flight once tile N+2
        # claims its slot.  Net effect: a pipeline depth of 2 dispatches
        # with reused host buffers.  tests/test_dedup_engine.py pins the
        # digests against the hashlib path on multi-tile input.
        _N_STAGING_SLOTS = 2
        slot_last: dict[tuple[int, int], tuple] = {}
        for blen, idxs in sorted(by_bucket.items()):
            for tile_no, start in enumerate(range(0, len(idxs), tile)):
                slot = tile_no % _N_STAGING_SLOTS
                prev = slot_last.get((blen, slot))
                if prev is not None:
                    with span("fdfs.engine.slot_wait", acc):
                        jax.block_until_ready(prev)
                group = idxs[start:start + tile]
                with span("fdfs.engine.pack", acc, True):
                    batch_buf = gear_cdc.staging_buffer(
                        tile * blen, slot=slot).reshape(tile, blen)
                    batch_buf[:] = 0
                    lens = np.zeros(tile, dtype=np.int32)
                    for row, i in enumerate(group):
                        off, ln = spans[i]
                        batch_buf[row, :ln] = arr[off:off + ln]
                        lens[row] = ln
                with span("fdfs.engine.dispatch", acc):
                    d, s = self._fingerprint_batch(batch_buf, lens)
                slot_last[(blen, slot)] = (d, s)
                groups.append(group)
                outs_d.append(d)
                outs_s.append(s)
        # ONE fetched array for the whole segment: digests (T,5) and
        # signatures (T,P) concatenate along axis 1 (both uint32) so the
        # fetch pays a single round-trip latency, then split on host.
        # The concat itself runs as ONE jitted call — as eager ops it
        # would be ~2 dispatches per tile.
        with span("fdfs.engine.fetch", acc):
            packed = np.asarray(jax.device_get(
                _packed_concat(len(outs_d))(*outs_d, *outs_s)))
        with span("fdfs.engine.scatter", acc, True):
            d_all = packed[:, :5]
            s_all = packed[:, 5:]
            for gi, group in enumerate(groups):
                base = gi * tile
                for row, i in enumerate(group):
                    digests[i] = d_all[base + row]
                    sigs[i] = s_all[base + row]
        return spans, digests, sigs

    def warmup(self) -> None:
        """Compile every jitted shape the fingerprint path can hit (one
        per pow2 length bucket) so the first real upload never pays a
        trace.  Call once at process start (the sidecar does, before it
        binds its socket)."""
        cfg = self.config
        blen = max(cfg.min_size, 1)
        while True:
            batch = np.zeros((cfg.row_tile, blen), dtype=np.uint8)
            lens = np.ones(cfg.row_tile, dtype=np.int32)
            d, s = self._fingerprint_batch(batch, lens)
            np.asarray(d), np.asarray(s)
            if blen >= cfg.max_size:
                break
            blen = min(blen << 1, cfg.max_size)

    # -- stateful ingest ---------------------------------------------------

    def ingest(self, data: bytes, file_ref: str, update_index: bool = True) -> IngestReport:
        """Full upload-path dedup: fingerprint, judge against the indexes,
        optionally commit new digests/signatures to them."""
        report = IngestReport(file_ref=file_ref, size=len(data))
        spans, digests, sigs = self.fingerprint(data)
        if not spans:
            return report

        raw = digest_bytes(digests)
        # Repeats *within* this stream must judge as duplicates even on a
        # dry run, so track first-seen digests locally too.
        seen_here: dict[bytes, list] = {}
        for i, (off, ln) in enumerate(spans):
            dig = raw[i * 20:(i + 1) * 20]
            existing = self.exact.lookup(dig)
            if existing is None:
                existing = seen_here.get(dig)
            if existing is None:
                seen_here[dig] = [file_ref, off]
                if update_index:
                    self.exact.insert(dig, [file_ref, off])
                report.chunks.append(ChunkRecord(off, ln, dig, duplicate=False))
            else:
                report.chunks.append(ChunkRecord(off, ln, dig, duplicate=True,
                                                 dup_of=existing))

        # File-level signature: min over chunk signatures == MinHash of the
        # union of their shingle sets.
        file_sig = sigs.min(axis=0)
        report.file_signature = file_sig
        report.near_dups = [
            (ref, score) for ref, score in self.near.query(
                file_sig, self.config.near_dup_top_k, self.config.near_dup_threshold)
            if ref != file_ref
        ]
        if update_index:
            self.near.add(file_sig, file_ref)
        return report

    # -- persistence -------------------------------------------------------

    def save(self, exact_path: str, near_path: str) -> None:
        self.exact.save(exact_path)
        self.near.save(near_path)

    @classmethod
    def load(cls, exact_path: str, near_path: str,
             config: DedupConfig | None = None) -> "DedupEngine":
        eng = cls(config)
        eng.exact = ExactDigestIndex.load(exact_path)
        eng.near = MinHashLSHIndex.load(near_path)
        return eng
