"""DedupEngine: the upload-path fingerprint pipeline.

Pipeline per ingested byte stream (north star; replaces the scalar CRC32
loop in the reference's ``storage/storage_dio.c:dio_write_file()``):

    bytes ──CDC (gear, position-parallel)──► chunk spans
          ──pad to pow2 buckets──► fixed-shape tiles (XLA-friendly)
          ──SHA1 batch + MinHash batch (one jit per tile shape)──►
          digests + signatures
          ──exact index──► per-chunk write/skip verdicts
          ──near index (on the device)──► file-level near-duplicates

Chunks are padded to power-of-two length buckets and shipped in tiles
whose row count comes from a short ladder under ``row_tile`` and under
one byte bound (``_TILE_MAX_BYTES``: at chunk widths of megabytes a tile
holds a few rows), chosen by what the request's buckets hold
(``tile_plan``): the shapes are a fixed set that follows from the
configured widths, all compiled in ``warmup()``, and a sparse bucket does
not ship a full tile of zeros.  Where rows are megabytes a launch costs
what its longest chunk costs, and the plan groups chunks by length so
that a request's long chunks walk together.
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

from fastdfs_tpu.dedup.index import ExactDigestIndex
from fastdfs_tpu.dedup.near_index import DeviceNearIndex
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
    # Rows of a full tile, where the tile's byte bound allows as many
    # (_row_ladder).  A bucket's rows ship in full tiles while it has
    # them; a sparse remainder takes the small rung (256 -> 32, at the
    # wide widths), so the jitted shapes stay a fixed set (plan_shapes) —
    # a free row count would retrace per distinct N and dominate
    # wall-clock.
    row_tile: int = 256
    # None = auto: Pallas kernels on TPU, XLA reference elsewhere.  The
    # two paths are bit-identical (tests/test_pallas_kernels.py).
    use_pallas: bool | None = None
    # Cut-selection policy: CDC_POLICY_DEFAULT (frozen, ref-identical) or
    # the opt-in CDC_POLICY_SKIPMIN.  NEVER change on a live index — the
    # policies are distinct content-address namespaces (the sidecar
    # discards snapshots on mismatch, same as a spec bump).
    cdc_policy: int = gear_cdc.CDC_POLICY_DEFAULT
    # Fingerprint fan-out: shard each (rows, blen) tile's rows over
    # this many local devices via parallel.make_fingerprint_step.
    # None = auto (all local devices when >1 and a TPU backend is up;
    # otherwise 1); 1 = single-device paths.  Every tile's row
    # count (plan_shapes) must divide by it.
    fan_out: int | None = None
    # (rows, seed): the near-dup index starts with this many seeded rows
    # on the device (near_index.py; the sidecar's --near-base).
    near_base: tuple[int, int] | None = None


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


def _widths(min_size: int, max_size: int) -> list[int]:
    """The tile widths: every value ``_bucket_len`` gives a chunk no
    longer than ``max_size``."""
    widths = [max(min_size, 1)]
    while widths[-1] < max_size:
        widths.append(min(widths[-1] << 1, max_size))
    return widths


# What one more tile costs the host whatever its size (two device_put,
# two kernel launches, the packing fusions' launches), in tile bytes
# that cost as much.  Measured on the v5e: a tile alone costs 1.05 ms +
# 0.39 ms per MB (2.7 MB); with two requests in the sidecar at once
# plans made at 2 and 4 MiB read alike and best, and under the served
# path a dispatch reads 1.5 ms whatever the tile holds (PERF.md
# section 6, PR 30).
_TILE_FIXED_BYTES = 4 << 20


# The most bytes a tile may hold: a full tile has ``row_tile`` rows only
# while that many fit.  The widest tile the shipped widths make is
# 256 x 64 KiB = 16 MiB, well under it; at chunk widths of megabytes
# (512 KiB - 8 MiB, what backup tools cut) it is what keeps a tile, its
# staging buffer and the words the SHA-1 step packs from growing with
# ``row_tile`` x ``max_size`` (2 GiB there).  Eight rows of the widest
# chunk the daemon may send (``max_size`` <= ``dedup_segment_bytes``,
# shipped 64M) would be over it, so ``DedupEngine`` refuses a
# ``max_size`` of which it does not hold eight rows.
_TILE_MAX_BYTES = 64 << 20


# What one 64-byte SHA-1 block costs a launch that walks it, in tile
# bytes that cost as much: 0.72 us on the v5e whatever the lanes carry
# (`%_sha1_rows_pallas.1` over the blocks its launches walked, PERF.md
# section 5, bottleneck 1), at ``_TILE_FIXED_BYTES``' 0.39 ms per MB:
# 0.72 us / 0.39 ns.  A byte walked costs 29 bytes shipped: at a width
# of kilobytes a launch walks under a millisecond and the fixed cost
# holds it; at megabytes the walk is what a plan pays.
_WALK_BLOCK_BYTES = 1846

# Rows from which a tile takes the lane-major SHA-1 kernel
# (ops/pallas_sha1.py:LANE); under it the row-major one, whose launch
# ends at its longest chunk.
_LANE_ROWS = 128


def _row_ladder(row_tile: int, blen: int) -> tuple[int, ...]:
    """Row counts a tile of width ``blen`` may have, largest first.  The
    full tile: ``row_tile`` rows, or as many as ``_TILE_MAX_BYTES`` holds
    at this width (whole groups of 8).  The small rung: an 8th of the
    full one, 8 rows at the least, where that is a whole multiple of 8
    (so it divides by any fan-out a v5e host has) and saves at least one
    tile's fixed cost against the full tile: a shape that saves less is
    two programs to compile for nothing.  At the shipped 256: (256, 32) at
    32K and 64K, (256,) at the narrower widths; at 512K - 8M: (128, 16),
    (64, 8), (32, 8), (16, 8), (8,); a ``row_tile`` under 64 is the only
    rung at any width up to 170K."""
    full = min(row_tile, max(1, _TILE_MAX_BYTES // blen))
    if full < row_tile and full > 8:
        full -= full % 8
    small = max(8, full // 8)
    if small < full and small % 8 == 0 and (
            (full - small) * blen >= _TILE_FIXED_BYTES):
        return (full, small)
    return (full,)


def _tiles_cost(tiles, blen: int) -> int:
    return len(tiles) * _TILE_FIXED_BYTES + sum(tiles) * blen


def _split_rows(n: int, rungs: tuple[int, ...], blen: int) -> list[int]:
    """Row counts of the tiles that carry ``n`` rows of width ``blen``:
    full tiles, and the remainder on small ones or rounded up to one
    more full tile, whichever costs less (at 64K: 40 rows -> 32 + 32,
    200 rows -> 256)."""
    full, rem = divmod(n, rungs[0])
    tiles = [rungs[0]] * full
    if rem:
        small = [rungs[-1]] * -(-rem // rungs[-1])
        one_full = [rungs[0]]
        tiles += (small if _tiles_cost(small, blen)
                  < _tiles_cost(one_full, blen) else one_full)
    return tiles


def _cheapest_tiles(n: int, blen: int, widths: list[int], row_tile: int
                    ) -> tuple[int, int, list[int]]:
    """``(cost, width, row counts)`` of the cheapest tiles for ``n`` rows
    no longer than ``blen``: at ``blen`` or at a wider width that has a
    smaller rung (24 rows of 16K ride one 32 x 32K tile, 1 MB, and not a
    256 x 16K one, 4 MB).  A chunk over ``max_size`` has only its own."""
    options = []
    for width in [w for w in widths if w >= blen] or [blen]:
        tiles = _split_rows(n, _row_ladder(row_tile, width), width)
        options.append((_tiles_cost(tiles, width), width, tiles))
    return min(options)


def _serial_width(row_tile: int, blen: int) -> bool:
    """True where ``_TILE_MAX_BYTES`` cuts the full tile of width
    ``blen`` under the lane-major kernel's rows: every tile of that
    width is a few rows of megabytes on the row-major kernel (at
    ``row_tile`` 256: 1 MiB and wider)."""
    return _TILE_MAX_BYTES // blen < min(row_tile, _LANE_ROWS)


def _walk_plan(lengths: list[int], widths: list[int], row_tile: int
               ) -> list[tuple[int, int, list[int]]]:
    """``tile_plan`` for a request that reaches the serial widths: its
    chunks in order of length, a run of them to a tile, so a tile holds
    chunks of like length and the long ones walk together.  A tile costs
    its bytes, the fixed cost and the blocks its launch walks
    (``launch_geometry``: under ``_LANE_ROWS`` rows its longest chunk's).
    best[j]: the cheapest (cost, start, rows, width) whose last tile ends
    the ``j`` shortest chunks; a tile of a rung takes as many chunks as
    it has rows (one chunk fewer in it never makes the rest cheaper)."""
    from fastdfs_tpu.ops.pallas_sha1 import launch_geometry

    order = sorted(range(len(lengths)), key=lengths.__getitem__)
    best: list[tuple[int, int, int, int]] = [(0, 0, 0, 0)]
    for j in range(1, len(order) + 1):
        longest = lengths[order[j - 1]]
        options = []
        for width in [w for w in widths if w >= longest] or [longest]:
            for rows in _row_ladder(row_tile, width):
                start = max(0, j - rows)
                walked = launch_geometry(rows, width, longest)[1]
                options.append((
                    best[start][0] + _TILE_FIXED_BYTES + rows * width
                    + walked * _WALK_BLOCK_BYTES, start, rows, width))
        best.append(min(options))
    plan = []
    j = len(order)
    while j:
        _, start, rows, width = best[j]
        plan.append((rows, width, order[start:j]))
        j = start
    return plan[::-1]


def tile_plan(lengths, min_size: int, max_size: int, row_tile: int
              ) -> list[tuple[int, int, list[int]]]:
    """The tiles one request ships, as ``(rows, blen, chunk indices)``.

    Chunks are grouped by pow2 length bucket and a tile's row count comes
    from ``_row_ladder`` by the rows there are, so sparse buckets ship a
    small tile and dense ones full tiles of ``row_tile`` rows.  A chunk
    may ride in any tile at least as wide as itself (rows are zero past
    their length and both kernels mask by ``lens``; a word's MinHash
    segment does not depend on the tile's width), so neighbouring
    buckets share tiles of the wider width where that is cheaper by
    ``_tiles_cost``: shipped bytes plus a fixed cost a tile.

    A launch also costs the device the SHA-1 blocks it walks one after
    another, and under 128 rows that is its longest chunk
    (``launch_geometry``; ``_WALK_BLOCK_BYTES`` a block, from the v5e's
    0.72 us).  Where a full tile has 128 rows and more a launch walks
    kilobytes, under a millisecond, which the fixed cost as measured
    already holds: those requests are planned by bytes alone, exactly as
    PR 30 tuned them on the chip.  A request with a chunk in a bucket
    whose full tile ``_TILE_MAX_BYTES`` cut under 128 rows
    (``_serial_width``: an observable of the widths, 1 MiB and wider at
    ``row_tile`` 256) is planned by ``_walk_plan`` with the walk priced
    in: its chunks by length, a run of them to a tile, so the longest
    chunks of narrower buckets fill the idle rows of a wider tile and a
    request's long chunks walk together once, instead of each bucket
    opening a launch as wide as its width.

    Every chunk is placed once and row 0 of every tile is a real chunk;
    every ``(rows, blen)`` is one of ``plan_shapes``.  Pure arithmetic,
    no JAX call.
    """
    lengths = list(lengths)
    widths = _widths(min_size, max_size)
    if lengths and _serial_width(
            row_tile, _bucket_len(max(lengths), min_size, max_size)):
        return _walk_plan(lengths, widths, row_tile)
    by_bucket: dict[int, list[int]] = {}
    for i, ln in enumerate(lengths):
        by_bucket.setdefault(_bucket_len(ln, min_size, max_size), []).append(i)
    blens = sorted(by_bucket)
    # best[j]: the cheapest (cost, groups) for the j narrowest buckets; a
    # group (i, j, width, row counts) ships buckets i..j-1 together.
    best: list[tuple[int, list]] = [(0, [])]
    for j in range(1, len(blens) + 1):
        n = 0
        options = []
        for i in range(j - 1, -1, -1):
            n += len(by_bucket[blens[i]])
            cost, width, tiles = _cheapest_tiles(n, blens[j - 1], widths,
                                                 row_tile)
            options.append((best[i][0] + cost,
                            best[i][1] + [(i, j, width, tiles)]))
        best.append(min(options, key=lambda o: o[0]))
    plan = []
    for i, j, width, tiles in best[-1][1]:
        idxs = [c for b in blens[i:j] for c in by_bucket[b]]
        start = 0
        for rows in tiles:
            plan.append((rows, width, idxs[start:start + rows]))
            start += rows
    return plan


@functools.lru_cache(maxsize=64)
def _zeros(n: int) -> memoryview:
    """``n`` read-only zero bytes, shared by every thread: one a tile
    width (the widths are a fixed set)."""
    return memoryview(bytes(n))


# Tiles from this width on are packed by numpy calls, which let the
# interpreter go while they copy; narrower ones by a memmove a row that
# keeps it.  A hand-off may cost a switch interval (5 ms) to win the
# interpreter back while other threads are in Python, so a tile of
# narrow rows copied by numpy costs its rows times the threads waiting;
# a memmove holds the others out for its copy, so wide rows copied that
# way lose the copies that would have overlapped.  A wide tile is
# mostly padding (64 MiB cut at restic's 512K/1M/8M: 101 MB of 168 MB
# shipped), so it is zeroed past its shortest chunk in one call before
# its rows are copied: one hand-off a row and one a tile.  Measured on
# an 8-core host (tools/bench_pack_convoy.py,
# bench_artifacts/pack_convoy.json), a request's pack in ms/MB: 10 MiB
# cut 2K/8K/64K, 20 threads and 2 more spinning in Python, 307 by numpy
# a row and 13.4 by memmove; 64 MiB cut at restic's widths, 4 threads,
# 0.43 this way against 2.54 by memmove every row, and 6.9 against 3.6
# with 2 more threads spinning.  Rows this wide are at most 4 a MB.
_RELEASE_ROW_BYTES = 256 << 10


def _pack_tile(buf: np.ndarray, src: memoryview, spans, group, rows: int,
               blen: int) -> tuple[np.ndarray, int]:
    """Pack the chunks ``group`` (indices into ``spans``, ``(offset,
    length)`` in the byte view ``src``) into the flat tile ``buf``
    (``rows * blen`` bytes of any contents), in the layout both kernels
    read: row ``r`` holds chunk ``group[r]`` and zeros to ``blen``, the
    rows after the last chunk only zeros.  Returns the tile's ``lens``
    (int32, one a row) and the rows copied by a call that let the
    interpreter go (all of a tile ``_RELEASE_ROW_BYTES`` wide or wider,
    none of a narrower one).  A narrow row's tail is zeroed from one
    shared zero buffer; the empty rows by one call."""
    lens = [spans[i][1] for i in group]
    n = len(group)
    at = 0
    if blen < _RELEASE_ROW_BYTES:
        released = 0
        dst = memoryview(buf)
        zeros = _zeros(blen)
        for i in group:
            off, ln = spans[i]
            dst[at:at + ln] = src[off:off + ln]
            dst[at + ln:at + blen] = zeros[:blen - ln]
            at += blen
    else:
        released = n
        buf.reshape(rows, blen)[:n, min(lens):] = 0
        for i in group:
            off, ln = spans[i]
            buf[at:at + ln] = np.frombuffer(src[off:off + ln], np.uint8)
            at += blen
    if n < rows:
        buf[n * blen:] = 0
    return np.array(lens + [0] * (rows - n), dtype=np.int32), released


def plan_shapes(cfg: DedupConfig) -> list[tuple[int, int]]:
    """Every ``(rows, blen)`` that ``tile_plan`` can emit at this
    geometry: what ``DedupEngine.warmup`` compiles."""
    return [(rows, blen) for blen in _widths(cfg.min_size, cfg.max_size)
            for rows in _row_ladder(cfg.row_tile, blen)]


@functools.lru_cache(maxsize=64)
def _packed_concat(half: int):
    """Jitted (digests..., sigs...) -> one (T, 5+P) array, cached per
    tile count (segment sizes repeat, so arities do too); the engine
    hands it tiles of one row count at a time."""
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
    accelerator; the exact index is the host's, the near-dup index the
    device's (``near_index.py``).  The verdicts gate disk writes in the
    storage daemon (write unique chunks, reference dups).
    """

    def __init__(self, config: DedupConfig | None = None) -> None:
        self.config = config or DedupConfig()
        if self.config.cdc_policy not in (gear_cdc.CDC_POLICY_DEFAULT,
                                          gear_cdc.CDC_POLICY_SKIPMIN):
            raise ValueError(f"unknown cdc_policy {self.config.cdc_policy}")
        self.exact = ExactDigestIndex()
        use_pallas = self.config.use_pallas
        if use_pallas is None:
            use_pallas = _tpu_available()
        # The near-dup index's arrays are made by its first use (warmup,
        # a commit), so an engine that is replaced by a loaded one
        # (``load``) never held them.
        self.near = DeviceNearIndex(self.config.num_perms,
                                    self.config.lsh_bands,
                                    self.config.near_base, use_pallas)
        # The survivor kernel is specialized to the default shingle
        # width; other widths take the (bit-identical) XLA reference.
        use_pallas = use_pallas and self.config.shingle == 5
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
        if not 0 < self.config.min_size < self.config.max_size:
            raise ValueError(f"chunk widths: min_size {self.config.min_size} "
                             f"must be under max_size {self.config.max_size}")
        if self.config.max_size * min(8, self.config.row_tile) > _TILE_MAX_BYTES:
            raise ValueError(
                f"max_size {self.config.max_size}: a tile holds "
                f"{_TILE_MAX_BYTES} bytes, under eight rows of that width")
        if any(rows % fan for rows, _ in plan_shapes(self.config)):
            raise ValueError(f"row_tile {self.config.row_tile} and its "
                             f"smaller tiles must divide by fan_out {fan}")
        # Resolved from the config's None = auto; the sidecar's `stats`
        # reply reports both.  The fan-out step runs the XLA reference
        # kernels under shard_map, so Pallas means the one-device path.
        self.fan_out = fan
        self.use_pallas = use_pallas and fan == 1
        self._fp_step = None  # built lazily: jitted multi-device step
        # Batch bytes by the device whose rows they were, read off the
        # result arrays' own shards: {device id: bytes}, and the tiles
        # placed by their row count: {rows: tiles} (how often the small
        # rungs engage).  fingerprint() runs on many connection threads
        # at once, hence the lock.
        self.device_bytes: dict[int, int] = {}
        self.tiles_by_rows: dict[int, int] = {}
        # Every tile's SHA-1 launch, summed (the arguments of its
        # fdfs.engine.dispatch span): rows that held a chunk, the lanes
        # the kernel's layout gives the tile, the 64-byte blocks it walks
        # one after another (under 128 rows: as far as its longest chunk)
        # and the blocks of its width, which it walked before the launch
        # ended early: walked / width is the share of that walk left
        # (ops/pallas_sha1.py:launch_geometry; the host path launches
        # nothing and counts the same arithmetic).
        # And the pack's work (_pack_tile): rows packed, of them those
        # copied by a call that let the interpreter go, chunk bytes
        # copied, and bytes zeroed (the rows' tails and the rows after a
        # tile's last chunk).
        self.launched = {"rows_placed": 0, "lanes_launched": 0,
                         "sha1_grid_steps": 0, "sha1_width_steps": 0,
                         "pack_rows": 0, "pack_rows_released": 0,
                         "pack_copied_bytes": 0, "pack_zeroed_bytes": 0}
        self._placed_lock = threading.Lock()

    def _count_placed(self, result, row_bytes: int) -> None:
        rows = result.shape[0]
        with self._placed_lock:
            self.tiles_by_rows[rows] = self.tiles_by_rows.get(rows, 0) + 1
            for shard in result.addressable_shards:
                dev = shard.device.id
                self.device_bytes[dev] = (self.device_bytes.get(dev, 0)
                                          + shard.data.shape[0] * row_bytes)

    def _fingerprint_batch(self, batch: np.ndarray, lens: np.ndarray):
        """Dispatch one (rows, blen) tile; returns device arrays
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
            from fastdfs_tpu.ops.pallas_sha1 import (default_sub,
                                                     sha1_batch_pallas)
            rows, blen = batch.shape
            # The rows cross as the words they already are on the host (a
            # view, no copy): packing bytes into words on the device is a
            # four-fold widening and a relayout that pads few rows to 128.
            if blen % 4 == 0:
                batch = batch.view(np.uint32)
            # ONE explicit transfer shared by both kernels: passing the
            # numpy batch to each jit would transfer it once per kernel.
            batch = jax.device_put(batch)
            lens = jax.device_put(lens)
            d = sha1_batch_pallas(batch, lens, blen, sub=default_sub(rows))
            s = minhash_batch_pallas(batch, lens, cfg.num_perms, cfg.shingle)
            self._count_placed(d, blen)
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
        src = memoryview(np.frombuffer(data, dtype=np.uint8))

        # A fixed set of (rows, blen) shapes, all compiled in warmup().
        # Transfer discipline (every device<->host transfer pays a fixed
        # latency, and device_put blocks the caller: 3 ms a 256 x 64K
        # tile on the v5e, PERF.md section 6, PR 26):
        #   * tile_plan() sizes each tile by the rows its bucket holds,
        #   * tiles are packed into REUSED thread-local staging buffers,
        #   * all tiles dispatch asynchronously,
        #   * digests and signatures are concatenated ON DEVICE so the
        #     whole segment costs exactly one fetch.
        # Device memory stays bounded by the segment size the daemon
        # streams (storage.conf:dedup_segment_bytes), not the file size.
        import jax

        from fastdfs_tpu.ops.pallas_sha1 import launch_geometry

        plan = tile_plan([ln for _, ln in spans], cfg.min_size, cfg.max_size,
                         cfg.row_tile)
        outs_d = []
        outs_s = []
        # Double-buffered staging: tiles dispatch
        # asynchronously and are fetched only once at the end, and PJRT
        # host-buffer semantics are backend-dependent — some clients
        # hold the host buffer zero-copy until the transfer completes.
        # Rotate 2 staging slots per buffer size AND block on the tile
        # that last used a slot before reusing it (its outputs being
        # ready implies its input transfer finished) — rotation alone
        # would still overwrite tile N while in flight once tile N+2
        # claims its slot.  Net effect: a pipeline depth of 2 dispatches
        # with reused host buffers.  Buffers are keyed by their byte
        # size, so two shapes of one size (256 x 8K, 32 x 64K) share
        # slots and slot_last is keyed the same way.
        # tests/test_dedup_engine.py pins the digests against the
        # hashlib path on multi-tile input.
        _N_STAGING_SLOTS = 2
        slot_last: dict[tuple[int, int], tuple] = {}
        tiles_of_size: dict[int, int] = {}
        by_rows: dict[int, list[int]] = {}   # rows -> its tiles' numbers
        for rows, blen, group in plan:
            size = rows * blen
            tile_no = tiles_of_size.get(size, 0)
            tiles_of_size[size] = tile_no + 1
            slot = tile_no % _N_STAGING_SLOTS
            prev = slot_last.get((size, slot))
            if prev is not None:
                with span("fdfs.engine.slot_wait", acc):
                    jax.block_until_ready(prev)
            with span("fdfs.engine.pack", acc, True,
                      rows=len(group)) as packing:
                staging = gear_cdc.staging_buffer(size, slot=slot)
                lens, released = _pack_tile(staging, src, spans, group,
                                            rows, blen)
                copied = int(lens.sum())
                packing.note(zeroed=size - copied)
            batch_buf = staging.reshape(rows, blen)
            lanes, width_blocks = launch_geometry(rows, blen)
            _, blocks = launch_geometry(rows, blen, int(lens.max()))
            with span("fdfs.engine.dispatch", acc, rows=len(group),
                      lanes=lanes, blen=blen, blocks=blocks,
                      width_blocks=width_blocks):
                d, s = self._fingerprint_batch(batch_buf, lens)
            with self._placed_lock:
                self.launched["rows_placed"] += len(group)
                self.launched["pack_rows"] += len(group)
                self.launched["pack_rows_released"] += released
                self.launched["pack_copied_bytes"] += copied
                self.launched["pack_zeroed_bytes"] += size - copied
                self.launched["lanes_launched"] += lanes
                self.launched["sha1_grid_steps"] += blocks
                self.launched["sha1_width_steps"] += width_blocks
            slot_last[(size, slot)] = (d, s)
            by_rows.setdefault(rows, []).append(len(outs_d))
            outs_d.append(d)
            outs_s.append(s)
        # ONE fetch for the whole segment: the tiles of one row count
        # concatenate ON DEVICE into one array, digests (T,5) beside
        # signatures (T,P) (both uint32), in ONE jitted call a row count
        # (as eager ops it would be ~2 dispatches per tile), and the
        # arrays, one a row count, come back in one device_get.  A
        # concat across row counts would be a new program for nearly
        # every mix of tiles a request can have; this way a program is
        # keyed by (tiles, rows), as few as before.
        with span("fdfs.engine.fetch", acc):
            packed = jax.device_get([
                _packed_concat(len(ts))(*(outs_d[t] for t in ts),
                                        *(outs_s[t] for t in ts))
                for ts in by_rows.values()])
        with span("fdfs.engine.scatter", acc, True):
            for (rows, ts), arr in zip(by_rows.items(), packed):
                for k, t in enumerate(ts):
                    group = plan[t][2]
                    out = arr[k * rows:k * rows + len(group)]
                    digests[group] = out[:, :5]
                    sigs[group] = out[:, 5:]
        return spans, digests, sigs

    def ec_encode(self, data: np.ndarray, m: int) -> bytes:
        """The m parity shards of the RS(k, m) stripe whose k data shards
        are ``data`` ((k, shard_len) uint8), end to end: the shards cross
        to the device through this thread's staging pool, the parity is
        ``ops/pallas_gf.py``'s kernel (no table, no gather), one fetch
        brings it back.  No index state is touched.  The program is
        compiled at a stripe length's first use, not in ``warmup``: a
        node that writes no stripe never pays for it."""
        from fastdfs_tpu.ops import pallas_gf, rs_code

        return pallas_gf.gf_matmul(rs_code.parity_matrix(len(data), m),
                                   data).tobytes()

    def warmup(self) -> None:
        """Compile every tile shape ``tile_plan`` can emit at the
        configured widths (every rung at every pow2 length bucket, each
        under the tile's byte bound) so no upload ever pays a trace.
        Call once at process start (the sidecar does, before it binds
        its socket)."""
        for rows, blen in plan_shapes(self.config):
            batch = np.zeros((rows, blen), dtype=np.uint8)
            lens = np.ones(rows, dtype=np.int32)
            d, s = self._fingerprint_batch(batch, lens)
            np.asarray(d), np.asarray(s)
        self.near.warmup()

    # -- stateful ingest ---------------------------------------------------

    def ingest(self, data: bytes, file_ref: str, update_index: bool = True) -> IngestReport:
        """Full upload-path dedup: fingerprint, judge against the indexes,
        optionally commit new digests/signatures to them."""
        report = IngestReport(file_ref=file_ref, size=len(data))
        spans, digests, sigs = self.fingerprint(data)
        if not spans:
            return report

        raw = digest_bytes(digests)
        digs = [raw[i * 20:(i + 1) * 20] for i in range(len(spans))]
        # Repeats *within* this stream must judge as duplicates even on a
        # dry run, so track first-seen digests locally too.
        seen_here: dict[bytes, list] = {}
        for (off, ln), dig, existing in zip(spans, digs,
                                            self.exact.lookup_batch(digs)):
            if existing is None:
                existing = seen_here.get(dig)
            if existing is None:
                seen_here[dig] = [file_ref, off]
                report.chunks.append(ChunkRecord(off, ln, dig, duplicate=False))
            else:
                report.chunks.append(ChunkRecord(off, ln, dig, duplicate=True,
                                                 dup_of=existing))
        if update_index:
            self.exact.insert_batch(raw, file_ref,
                                    [off for off, _ in spans])

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
        eng.near = DeviceNearIndex.load(near_path, eng.config.near_base,
                                        eng.near.use_pallas)
        return eng
