"""Dedup engine behavior: exact dedup verdicts, near-dup detection,
snapshot/restore, and verdict correctness vs a trivial CPU referee."""

import hashlib

import numpy as np
import pytest

from fastdfs_tpu.dedup import DedupConfig, DedupEngine
from fastdfs_tpu.dedup import engine as engine_mod
from fastdfs_tpu.dedup.engine import plan_shapes, tile_plan
from fastdfs_tpu.dedup.index import ExactDigestIndex, MinHashLSHIndex
from fastdfs_tpu.ops import gear_cdc
from fastdfs_tpu.ops import minhash as M

CFG = DedupConfig(min_size=64, avg_bits=8, max_size=1024)


def _rand(rng, n):
    return rng.randint(0, 256, size=n, dtype=np.uint8).tobytes()


def test_fingerprint_digests_match_hashlib():
    rng = np.random.RandomState(1)
    data = _rand(rng, 20_000)
    eng = DedupEngine(CFG)
    spans, digests, _ = eng.fingerprint(data)
    assert sum(ln for _, ln in spans) == len(data)
    raw = digests.astype(">u4").tobytes()
    for i, (off, ln) in enumerate(spans):
        assert raw[i * 20:(i + 1) * 20] == hashlib.sha1(data[off:off + ln]).digest()


def test_fingerprint_multi_tile_digests_match_hashlib():
    """Backend-pinning regression: dispatch MANY tiles per
    bucket so the rotated staging buffers are reused across
    asynchronously-dispatched batches — if a backend ever holds the host
    buffer zero-copy past dispatch, a reused buffer would corrupt an
    earlier tile's digests and this comparison fails loudly."""
    # Tiny row tile => a few thousand chunks span dozens of tile groups
    # per pow2 bucket, exercising slot reuse (tile_no % 2) many times.
    cfg = DedupConfig(min_size=64, avg_bits=8, max_size=1024, row_tile=16)
    rng = np.random.RandomState(7)
    data = _rand(rng, 300_000)
    eng = DedupEngine(cfg)
    spans, digests, sigs = eng.fingerprint(data)
    assert sum(ln for _, ln in spans) == len(data)
    n_tiles = -(-len(spans) // cfg.row_tile)
    assert n_tiles > 2 * 2, "input too small to exercise slot reuse"
    raw = digests.astype(">u4").tobytes()
    for i, (off, ln) in enumerate(spans):
        assert raw[i * 20:(i + 1) * 20] == \
            hashlib.sha1(data[off:off + ln]).digest(), f"chunk {i} corrupted"
    assert sigs.shape == (len(spans), cfg.num_perms)


def test_exact_dedup_same_file_twice():
    rng = np.random.RandomState(2)
    data = _rand(rng, 30_000)
    eng = DedupEngine(CFG)
    r1 = eng.ingest(data, "f1")
    assert r1.bytes_duplicate == 0
    r2 = eng.ingest(data, "f2")
    assert r2.dedup_ratio == 1.0
    assert all(c.duplicate for c in r2.chunks)
    assert r2.chunks[0].dup_of == ["f1", 0]
    # identical content => file-level near-dup at similarity 1.0
    assert any(ref == "f1" and score == 1.0 for ref, score in r2.near_dups)


def test_partial_overlap_dedup():
    rng = np.random.RandomState(3)
    shared = _rand(rng, 16_000)
    a = shared + _rand(rng, 8_000)
    b = _rand(rng, 8_000) + shared
    eng = DedupEngine(CFG)
    eng.ingest(a, "a")
    r = eng.ingest(b, "b")
    # CDC re-synchronizes inside `shared`, so most shared bytes dedup.
    assert r.bytes_duplicate > len(shared) * 0.6
    assert 0 < r.dedup_ratio < 1


def test_unique_content_no_dedup():
    rng = np.random.RandomState(4)
    eng = DedupEngine(CFG)
    eng.ingest(_rand(rng, 10_000), "x")
    r = eng.ingest(_rand(rng, 10_000), "y")
    assert r.bytes_duplicate == 0
    assert r.near_dups == []


def test_near_dup_without_exact_match():
    rng = np.random.RandomState(5)
    base = np.frombuffer(_rand(rng, 20_000), dtype=np.uint8).copy()
    eng = DedupEngine(CFG)
    eng.ingest(base.tobytes(), "orig")
    mutated = base.copy()
    for pos in range(0, len(mutated), 1500):  # sprinkle single-byte edits
        mutated[pos] ^= 0xFF
    r = eng.ingest(mutated.tobytes(), "edit")
    assert any(ref == "orig" and score >= 0.5 for ref, score in r.near_dups)


def test_ingest_without_index_update_is_pure():
    rng = np.random.RandomState(6)
    data = _rand(rng, 5_000)
    eng = DedupEngine(CFG)
    eng.ingest(data, "probe", update_index=False)
    assert len(eng.exact) == 0 and len(eng.near) == 0
    r = eng.ingest(data, "real")
    assert r.bytes_duplicate == 0  # probe left no trace


def test_empty_stream():
    eng = DedupEngine(CFG)
    r = eng.ingest(b"", "empty")
    assert r.size == 0 and r.chunks == [] and r.dedup_ratio == 0.0


def test_dry_run_sees_in_stream_repeats():
    # update_index=False must still judge repeats within the same stream
    # (review finding: dedup estimation was systematically low).
    data = b"z" * (1024 * 4)  # constant -> identical forced-max chunks
    eng = DedupEngine(CFG)
    r = eng.ingest(data, "dry", update_index=False)
    r2 = DedupEngine(CFG).ingest(data, "wet", update_index=True)
    assert r.bytes_duplicate == r2.bytes_duplicate > 0
    assert len(eng.exact) == 0


def test_snapshot_paths_without_npz_suffix(tmp_path):
    # save/load must round-trip whatever path the caller passed
    # (review finding: np.savez appends .npz, np.load did not).
    rng = np.random.RandomState(70)
    data = _rand(rng, 8_000)
    eng = DedupEngine(CFG)
    eng.ingest(data, "f1")
    ep, np_ = str(tmp_path / "exact"), str(tmp_path / "near")
    eng.save(ep, np_)
    eng2 = DedupEngine.load(ep, np_, CFG)
    assert eng2.ingest(data, "f2").dedup_ratio == 1.0
    # no stray temp files left behind (atomic write-then-rename)
    leftovers = [p.name for p in tmp_path.iterdir() if ".tmp" in p.name]
    assert leftovers == []


def test_lsh_query_after_load_matches(tmp_path):
    idx = MinHashLSHIndex(64, 16)
    rng = np.random.RandomState(71)
    sigs = rng.randint(0, 2**32, size=(20, 64), dtype=np.uint64).astype(np.uint32)
    for i, s in enumerate(sigs):
        idx.add(s, f"ref{i}")
    idx.save(str(tmp_path / "lsh"))
    idx2 = MinHashLSHIndex.load(str(tmp_path / "lsh"))
    assert len(idx2) == 20
    got = idx2.query(sigs[7], top_k=1, min_similarity=0.9)
    assert got and got[0][0] == "ref7" and got[0][1] == 1.0
    assert np.array_equal(idx2.signatures, idx.signatures)


def test_engine_snapshot_roundtrip(tmp_path):
    rng = np.random.RandomState(7)
    data = _rand(rng, 15_000)
    eng = DedupEngine(CFG)
    eng.ingest(data, "f1")
    ep, np_ = str(tmp_path / "exact.npz"), str(tmp_path / "near.npz")
    eng.save(ep, np_)

    eng2 = DedupEngine.load(ep, np_, CFG)
    r = eng2.ingest(data, "f2")
    assert r.dedup_ratio == 1.0  # restored index still dedups
    assert any(ref == "f1" for ref, _ in r.near_dups)


def test_exact_index_basics():
    idx = ExactDigestIndex()
    d = hashlib.sha1(b"x").digest()
    assert idx.insert(d, "a") is True
    assert idx.insert(d, "b") is False  # first writer wins
    assert idx.lookup(d) == "a"
    assert idx.lookup_batch([d, b"\x00" * 20]) == ["a", None]
    assert idx.remove(d) is True and idx.remove(d) is False


def test_lsh_index_validation():
    with pytest.raises(ValueError):
        MinHashLSHIndex(num_perms=64, bands=10)
    idx = MinHashLSHIndex(64, 16)
    with pytest.raises(ValueError):
        idx.add(np.zeros(32, np.uint32), "bad")


def test_chunk_spans_respect_geometry():
    rng = np.random.RandomState(8)
    data = _rand(rng, 50_000)
    eng = DedupEngine(CFG)
    spans, _, _ = eng.fingerprint(data)
    for off, ln in spans[:-1]:
        assert CFG.min_size <= ln <= CFG.max_size
    # spans tile the stream exactly
    assert spans[0][0] == 0
    for (o1, l1), (o2, _) in zip(spans, spans[1:]):
        assert o1 + l1 == o2


def test_cuts_match_reference_through_engine():
    rng = np.random.RandomState(9)
    data = _rand(rng, 40_000)
    eng = DedupEngine(CFG)
    spans, _, _ = eng.fingerprint(data)
    cuts = [off + ln for off, ln in spans]
    assert cuts == gear_cdc.chunk_stream_ref(data, CFG.min_size, CFG.avg_bits,
                                             CFG.max_size)


def test_empty_signature_is_not_indexed_and_never_matches():
    # A no-survivor sketch carries no similarity information; indexing it
    # would make every such item a 1.0-score "near-dup" of every other.
    from fastdfs_tpu.dedup.index import MinHashLSHIndex
    from fastdfs_tpu.ops.minhash import EMPTY

    idx = MinHashLSHIndex(64, 16)
    empty = np.full(64, EMPTY, dtype=np.uint32)
    assert idx.add(empty, "a") == -1
    assert len(idx) == 0
    assert idx.query(empty) == []
    real = np.arange(64, dtype=np.uint32)
    assert idx.add(real, "b") == 0
    assert idx.query(empty) == []


def test_stale_signature_spec_snapshot_rejected(tmp_path):
    # v1 snapshots (no sig_spec field) hold incompatible signatures; the
    # load must fail loudly instead of silently scoring noise.
    from fastdfs_tpu.dedup.index import MinHashLSHIndex

    p = str(tmp_path / "near.npz")
    np.savez_compressed(
        p, sigs=np.zeros((1, 64), np.uint32),
        refs=np.array(['"x"'], dtype=object), num_perms=64, bands=16)
    with pytest.raises(ValueError, match="spec-v1"):
        MinHashLSHIndex.load(p)


# -- tile plan: a tile has as many rows as its bucket holds ----------------

SHIPPED = DedupConfig()


def _np_signature(chunk: bytes, perms: int, k: int) -> np.ndarray:
    """NumPy MinHash (spec v2) of one chunk, written from the spec and not
    from the kernels: shingle hashes, 1/256 survivors, the least survivor
    per (word index mod NUM_SEGMENTS), then the min of a*x+b over those."""
    buf = np.frombuffer(chunk, np.uint8)
    n = len(buf)
    d = np.concatenate([buf, np.zeros(k, np.uint8)]).astype(np.uint32)
    h = np.zeros(n, np.uint32)
    with np.errstate(over="ignore"):
        for j in range(k):
            h = h * M._POLY_B + d[j:j + n]
        bound = n - k if n >= k else max(n, 1) - 1
        pos = np.nonzero((h[:bound + 1] & M.SAMPLE_MASK) == 0)[0]
        z = np.full(M.NUM_SEGMENTS, M.EMPTY, np.uint32)
        np.minimum.at(z, (pos // 4) % M.NUM_SEGMENTS, h[pos])
        z = z[z != M.EMPTY]
        a, b = M._perm_constants(perms)
        if not len(z):
            return np.full(perms, M.EMPTY, np.uint32)
        return (z[None, :] * a[:, None] + b[:, None]).min(axis=1)


def _record_tiles(monkeypatch):
    """Record the (rows, blen) of every tile an engine dispatches."""
    seen = []
    real = DedupEngine._fingerprint_batch

    def recording(self, batch, lens):
        seen.append(batch.shape)
        return real(self, batch, lens)
    monkeypatch.setattr(DedupEngine, "_fingerprint_batch", recording)
    return seen


# row_tile 64 -> rungs (64, 8).  The fixed cost of a tile is set in the
# test: at these toy widths the shipped 4 MiB would fold every request
# into one full tile and no small rung would exist.  Rows in the 1024
# bucket: 1, rung - 1, rung, rung + 1, row_tile + 1; the last case rounds
# 34 rows up to one full tile because four more tiles would cost more.
TILE_CASES = {
    (1, 0): [(8, 1024)],
    (7, 0): [(8, 128), (8, 1024)],
    (8, 0): [(8, 128), (8, 1024)],
    (9, 0): [(8, 1024), (8, 1024)],
    (65, 0): [(64, 1024), (8, 1024)],
    (30, 16384): [(64, 1024)],
}


@pytest.mark.parametrize("n_rows,fixed_bytes", sorted(TILE_CASES))
def test_tiles_sized_by_bucket_rows_match_hashlib_and_numpy(
        monkeypatch, n_rows, fixed_bytes):
    monkeypatch.setattr(engine_mod, "_TILE_FIXED_BYTES", fixed_bytes)
    cfg = DedupConfig(min_size=64, avg_bits=8, max_size=1024, row_tile=64,
                      use_pallas=False)
    assert engine_mod._row_ladder(cfg.row_tile, 1024) == (64, 8)
    rng = np.random.RandomState(100 + n_rows)
    # n_rows chunks in the 1024 bucket, three in the 128 bucket, one of 3
    # bytes (shorter than a shingle) in the 64 bucket: the narrow ones
    # ride in a wider tile wherever its padding rows have room.
    lens = ([int(x) for x in rng.randint(513, 1025, size=n_rows)]
            + [100, 128, 65, 3])
    rng.shuffle(lens)
    data = _rand(rng, sum(lens))
    cuts = np.cumsum(lens).tolist()
    seen = _record_tiles(monkeypatch)
    spans, digests, sigs = DedupEngine(cfg).fingerprint(data, cuts=cuts)
    assert [ln for _, ln in spans] == lens
    raw = digests.astype(">u4").tobytes()
    for i, (off, ln) in enumerate(spans):
        chunk = data[off:off + ln]
        assert raw[i * 20:(i + 1) * 20] == hashlib.sha1(chunk).digest(), i
        np.testing.assert_array_equal(
            sigs[i], _np_signature(chunk, cfg.num_perms, cfg.shingle), str(i))
    assert seen == TILE_CASES[n_rows, fixed_bytes]
    assert set(seen) <= set(plan_shapes(cfg))


def _bucket_lens(counts: dict[int, int]) -> list[int]:
    """Chunk lengths with ``counts[blen]`` chunks in each pow2 bucket."""
    rng = np.random.RandomState(5)
    lens = [int(rng.randint(max(blen // 2, SHIPPED.min_size - 1) + 1, blen + 1))
            for blen, n in counts.items() for _ in range(n)]
    rng.shuffle(lens)
    return lens


K = 1024
# Bucket counts of the reference CDC on seeded random bytes (PERF.md
# section 6, PR 30) and what such a request may ship at the most: the
# full-tile plan shipped 23.4 MB for a 200K file and 32.6 MB for 1M.
PLAN_CASES = {
    "200K": ({4 * K: 1, 8 * K: 3, 16 * K: 7, 32 * K: 3, 64 * K: 1}, 2.2e6),
    "200K_no_64K": ({4 * K: 6, 8 * K: 9, 16 * K: 7, 32 * K: 2}, 2.2e6),
    "1M": ({2 * K: 1, 4 * K: 24, 8 * K: 35, 16 * K: 28, 32 * K: 14,
            64 * K: 4}, 7e6),
    "10M": ({2 * K: 1, 4 * K: 227, 8 * K: 314, 16 * K: 318, 32 * K: 159,
             64 * K: 17}, 26e6),
    "one_chunk": ({2 * K: 1}, 1e6),
    "small_16K": ({8 * K: 4, 16 * K: 5}, 1.1e6),   # rides one 32 x 32K tile
    "dense_64K": ({64 * K: 513}, (512 + 32) * 64 * K),
}


@pytest.mark.parametrize("case", sorted(PLAN_CASES))
def test_tile_plan_places_every_chunk_once(case):
    counts, ceiling = PLAN_CASES[case]
    lens = _bucket_lens(counts)
    plan = tile_plan(lens, SHIPPED.min_size, SHIPPED.max_size,
                     SHIPPED.row_tile)
    placed = sorted(i for _, _, group in plan for i in group)
    assert placed == list(range(len(lens)))
    shapes = set(plan_shapes(SHIPPED))
    for rows, blen, group in plan:
        assert (rows, blen) in shapes
        assert 1 <= len(group) <= rows          # row 0 is a real chunk
        assert all(lens[i] <= blen for i in group)
    shipped = sum(rows * blen for rows, blen, _ in plan)
    assert shipped <= ceiling
    # Never dearer than a full tile a bucket (the plan before PR 30), by
    # the plan's own cost: shipped bytes + a fixed cost a tile.
    full = sum(-(-n // SHIPPED.row_tile) for n in counts.values())
    full_bytes = sum(-(-n // SHIPPED.row_tile) * SHIPPED.row_tile * blen
                     for blen, n in counts.items())
    fixed = engine_mod._TILE_FIXED_BYTES
    assert shipped + len(plan) * fixed <= full_bytes + full * fixed
    # Padding: under the smallest rung of its width, or less than the
    # tiles that would avoid it cost (a remainder of 200 rows rounds up
    # to one full tile: seven small ones would cost more than 56 rows).
    for rows, blen, group in plan:
        smallest = engine_mod._row_ladder(SHIPPED.row_tile, blen)[-1]
        spare = rows - len(group)
        assert spare < smallest or (
            spare * blen < -(-len(group) // smallest) * fixed)


def test_single_rung_below_64_rows_is_the_plan_before():
    """row_tile 8 and 16 (this file's other tests, test_cdc_kernels.py)
    have one rung: whole tiles of row_tile rows."""
    for row_tile in (8, 16):
        for blen in (64, 1024, 65536):
            assert engine_mod._row_ladder(row_tile, blen) == (row_tile,)
    plan = tile_plan([700] * 40, 64, 1024, 16)
    assert [(rows, blen, len(g)) for rows, blen, g in plan] == [
        (16, 1024, 16), (16, 1024, 16), (16, 1024, 8)]


def test_warmup_dispatches_exactly_the_shapes_the_plan_can_emit(monkeypatch):
    seen = []
    monkeypatch.setattr(
        DedupEngine, "_fingerprint_batch",
        lambda self, batch, lens: (
            seen.append(batch.shape) or
            (np.zeros((batch.shape[0], 5), np.uint32),
             np.zeros((batch.shape[0], SHIPPED.num_perms), np.uint32))))
    DedupEngine(DedupConfig(use_pallas=False)).warmup()
    assert len(seen) == len(set(seen))
    emitted = set()
    widths = sorted({blen for _, blen in seen})
    rng = np.random.RandomState(11)
    requests = [[blen] * n for blen in widths
                for n in (1, 8, 9, 32, 33, 200, 256, 257, 300)]
    for _ in range(200):    # mixed requests, sparse to dense
        requests.append([int(rng.choice(widths)) - int(rng.randint(0, 1000))
                         for _ in range(int(rng.choice([3, 20, 100, 700])))])
    for lens in requests:
        emitted |= {(rows, blen) for rows, blen, _ in tile_plan(
            lens, SHIPPED.min_size, SHIPPED.max_size, SHIPPED.row_tile)}
    assert emitted == set(seen)


# (rows, blen, chunk lengths of the body, the chunks the tile holds in
# row order).  "narrower_bucket": a full 1024-wide tile whose rows are
# mostly under half its width; "fewer_chunks": 3 chunks on 64 rows;
# "ends_at_body_end": the body's last chunk in the tile, beside one as
# wide as the tile; "serial_width": restic's widths, a tile of 8 rows of
# 4 MiB (a serial width: few rows of megabytes), packed by numpy, with a
# chunk of 100 KiB beside three of megabytes and four empty rows.
PACK_CASES = {
    "narrower_bucket": (8, 1024, [700, 100, 300, 65, 511, 900, 3, 128],
                        [1, 0, 3, 5, 6, 7, 2, 4]),
    "fewer_chunks": (64, 1024, [513, 1024, 640, 77, 999, 1000, 800, 700],
                     [2, 5, 7]),
    "ends_at_body_end": (8, 4096, [4096, 1000, 2500], [2, 0]),
    "serial_width": (8, 4 << 20, [600 << 10, 4 << 20, 1536 << 10, 100 << 10],
                     [3, 0, 2, 1]),
}


@pytest.mark.parametrize("case", sorted(PACK_CASES))
def test_pack_tile_writes_the_layout_the_kernels_read(case):
    """Into a slot that held other bytes: each row its chunk, then zeros
    to the width; the rows after the last chunk all zeros."""
    rows, blen, lens, group = PACK_CASES[case]
    data = _rand(np.random.RandomState(len(case)), sum(lens))
    spans = list(zip(np.cumsum([0] + lens[:-1]).tolist(), lens))
    want = np.zeros((rows, blen), np.uint8)
    for row, i in enumerate(group):
        off, ln = spans[i]
        want[row, :ln] = np.frombuffer(data, np.uint8, ln, off)
    buf = np.full(rows * blen, 0xAB, np.uint8)
    src = memoryview(np.frombuffer(data, np.uint8))
    got, released = engine_mod._pack_tile(buf, src, spans, group, rows, blen)
    assert buf.tobytes() == want.tobytes()
    assert got.dtype == np.int32
    assert got.tolist() == [lens[i] for i in group] + [0] * (rows - len(group))
    # a tile this wide has its rows copied by calls that let the
    # interpreter go, a narrower one none
    if case == "serial_width":
        assert engine_mod._serial_width(256, blen)
        assert blen >= engine_mod._RELEASE_ROW_BYTES and released == 4
    else:
        assert blen < engine_mod._RELEASE_ROW_BYTES and released == 0


def test_requests_in_a_row_reuse_dirty_slots_and_stay_exact(monkeypatch):
    """Two requests on one thread: the second's tiles land in the staging
    slots the first left full of its bytes, with shorter chunks and empty
    rows; every tile dispatched is zero past its rows' lengths (the
    kernels' contract: the host path here hashes each row's chunk alone,
    the device's SHA-1 reads the padding), and the digests are hashlib's
    and the signatures minhash_batch's over clean rows."""
    cfg = DedupConfig(min_size=64, avg_bits=8, max_size=1024, row_tile=16,
                      use_pallas=False)
    eng = DedupEngine(cfg)
    real = DedupEngine._fingerprint_batch

    def zero_past_lens(self, batch, lens):
        past = np.arange(batch.shape[1]) >= np.asarray(lens)[:, None]
        assert not batch[past].any()
        return real(self, batch, lens)
    monkeypatch.setattr(DedupEngine, "_fingerprint_batch", zero_past_lens)
    rng = np.random.RandomState(43)
    first = [1024] * 40 + [128] * 20
    second = [int(x) for x in rng.randint(513, 700, size=37)] + [65, 100]
    for lens in (first, second):
        data = _rand(rng, sum(lens))
        spans, digests, sigs = eng.fingerprint(
            data, cuts=np.cumsum(lens).tolist())
        raw = digests.astype(">u4").tobytes()
        clean = np.zeros((len(lens), max(lens)), np.uint8)
        for i, (off, ln) in enumerate(spans):
            assert raw[i * 20:(i + 1) * 20] == hashlib.sha1(
                data[off:off + ln]).digest(), i
            clean[i, :ln] = np.frombuffer(data, np.uint8, ln, off)
        np.testing.assert_array_equal(sigs, np.asarray(M.minhash_batch(
            clean, np.array(lens, np.int32), cfg.num_perms, cfg.shingle)))
        if lens is first:
            keys = gear_cdc.staging_buffer_stats()["keys"]
    # the second request packed into the first's buffers, not new ones
    assert gear_cdc.staging_buffer_stats()["keys"] == keys
    assert eng.launched["pack_rows"] == len(first) + len(second)
