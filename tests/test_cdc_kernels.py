"""CDC kernel family: goldens, policy equivalence, fan-out.

ISSUE 13's safety net around the ingest hot path:

- **Cut-stability golden** (tests/goldens/cdc_cuts.json): seeded corpora
  pinned to exact cut offsets under BOTH policies plus a SHA1 of the
  windowed gear-hash stream.  Cuts are content addresses — silent drift
  would zero out every dedup index fleet-wide — so the serial referee,
  the NumPy path, and the jax path are all pinned byte-for-byte against
  the checked-in fixture (wired into tools/fdfs_lint.py FIXTURE_GOLDENS).
- **Kernel equivalence properties** on adversarial inputs (empty, short,
  all-zero, all-identical, lane/tile boundary lengths) across
  ref/NumPy/jax, including skip-min (``cdc_policy=2``) against its own
  serial referee ``chunk_stream_skipmin_ref``.
- **Multi-chip fan-out**: ``parallel.make_fingerprint_step`` over the
  virtual 8-device CPU mesh is bit-identical to hashlib SHA1 + the XLA
  MinHash, and ``DedupEngine(fan_out=8)`` matches ``fan_out=1``.
- **staging_buffer growth audit**: repeated ``chunk_stream_np`` calls
  reuse one fixed work-buffer pair; the engine's 2-slot device staging
  rotation does not realloc per call.
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import pytest

from fastdfs_tpu.ops import gear_cdc as gc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN_PATH = os.path.join(REPO, "tests", "goldens", "cdc_cuts.json")


def _corpus(kind: str, seed: int, length: int) -> bytes:
    """The fixture's corpus recipe — must stay in lockstep with the
    'corpus' field of cdc_cuts.json."""
    rng = np.random.RandomState(seed)
    if kind == "random":
        return rng.randint(0, 256, length, dtype=np.uint8).tobytes()
    if kind == "lowentropy":
        return rng.randint(0, 16, length, dtype=np.uint8).tobytes()
    if kind == "repetitive":
        tile = rng.randint(0, 256, 512, dtype=np.uint8).tobytes()
        return (tile * (length // len(tile) + 1))[:length]
    raise ValueError(kind)


def _golden():
    with open(GOLDEN_PATH) as fh:
        return json.load(fh)


def _check_valid_cuts(cuts, n, min_size, max_size, policy):
    """Structural invariants every policy shares."""
    if n == 0:
        assert cuts == []
        return
    assert cuts[-1] == n
    last = 0
    for i, c in enumerate(cuts):
        size = c - last
        assert size > 0
        assert size <= max_size
        if i < len(cuts) - 1:  # every chunk but the tail honors min_size
            assert size >= min(min_size, n)
        last = c


# ---------------------------------------------------------------------------
# golden pinning
# ---------------------------------------------------------------------------

def test_golden_spec_version():
    assert _golden()["cdc_spec"] == gc.CDC_SPEC_VERSION


@pytest.mark.parametrize("case", _golden()["cases"],
                         ids=[c["name"] for c in _golden()["cases"]])
def test_golden_gear_hash_stream(case):
    data = _corpus(case["kind"], case["seed"], case["length"])
    dig = hashlib.sha1(
        gc.gear_hashes_np(data).astype("<u4").tobytes()).hexdigest()
    assert dig == case["gear_sha1"]


@pytest.mark.parametrize("case", _golden()["cases"],
                         ids=[c["name"] for c in _golden()["cases"]])
def test_golden_cuts_default_all_paths(case):
    data = _corpus(case["kind"], case["seed"], case["length"])
    geo = (case["min_size"], case["avg_bits"], case["max_size"])
    want = case["cuts_default"]
    assert gc.chunk_stream_ref(data, *geo) == want
    assert gc.chunk_stream_np(data, *geo) == want
    assert gc.chunk_stream(data, *geo) == want


@pytest.mark.parametrize("case", _golden()["cases"],
                         ids=[c["name"] for c in _golden()["cases"]])
def test_golden_cuts_skipmin_all_paths(case):
    data = _corpus(case["kind"], case["seed"], case["length"])
    geo = (case["min_size"], case["avg_bits"], case["max_size"])
    want = case["cuts_skipmin"]
    assert gc.chunk_stream_skipmin_ref(data, *geo) == want
    assert gc.chunk_stream_np(data, *geo,
                              cdc_policy=gc.CDC_POLICY_SKIPMIN) == want
    assert gc.chunk_stream(data, *geo,
                           cdc_policy=gc.CDC_POLICY_SKIPMIN) == want


def test_golden_policies_actually_diverge():
    """The fixture must witness that skip-min is a DIFFERENT address
    namespace — at least one case with different cuts."""
    cases = _golden()["cases"]
    assert any(c["cuts_default"] != c["cuts_skipmin"] for c in cases)


# ---------------------------------------------------------------------------
# kernel equivalence properties (adversarial inputs)
# ---------------------------------------------------------------------------

def _adversarial_buffers():
    rng = np.random.RandomState(99)
    yield "empty", b""
    yield "one", b"\x42"
    yield "below_min", rng.randint(0, 256, 63, dtype=np.uint8).tobytes()
    yield "all_zero", bytes(10000)
    yield "all_identical", b"\xab" * 10000
    # lane-fold boundary (jax folds at _LANE_MIN_BYTES, multiples of 256)
    for n in (gc._LANE_MIN_BYTES - 1, gc._LANE_MIN_BYTES,
              gc._LANE_MIN_BYTES + 1, 4 * gc._LANE_MIN_BYTES):
        yield f"lane_{n}", rng.randint(0, 256, n, dtype=np.uint8).tobytes()
    # host scan tile boundary (NumPy path tiles at _NP_TILE)
    for n in (gc._NP_TILE - 1, gc._NP_TILE, gc._NP_TILE + 1):
        yield f"tile_{n}", rng.randint(0, 256, n, dtype=np.uint8).tobytes()


@pytest.mark.parametrize("name,data", list(_adversarial_buffers()),
                         ids=[n for n, _ in _adversarial_buffers()])
def test_paths_identical_default_policy(name, data):
    geo = (64, 8, 1024)
    want = gc.chunk_stream_ref(data, *geo) if data else []
    got_np = gc.chunk_stream_np(data, *geo)
    got_jax = gc.chunk_stream(data, *geo)
    assert got_np == want
    assert got_jax == want
    _check_valid_cuts(want, len(data), geo[0], geo[2], 1)


@pytest.mark.parametrize("name,data", list(_adversarial_buffers()),
                         ids=[n for n, _ in _adversarial_buffers()])
def test_paths_identical_skipmin_policy(name, data):
    geo = (64, 8, 1024)
    want = gc.chunk_stream_skipmin_ref(data, *geo) if data else []
    got_np = gc.chunk_stream_np(data, *geo, cdc_policy=2)
    got_jax = gc.chunk_stream(data, *geo, cdc_policy=2)
    assert got_np == want
    assert got_jax == want
    _check_valid_cuts(want, len(data), geo[0], geo[2], 2)


def test_gear_hashes_lane_fold_bit_identical():
    """The (LANES, cols) halo fold must equal the serial rolling hash at
    every position, including across row seams."""
    rng = np.random.RandomState(5)
    for n in (gc._LANE_MIN_BYTES, 4 * gc._LANE_MIN_BYTES):
        data = rng.randint(0, 256, n, dtype=np.uint8)
        assert (np.asarray(gc.gear_hashes(data))
                == gc.gear_hashes_np(data)).all()
    # small (un-folded) shape pins vs the serial byte-loop referee
    data = rng.randint(0, 256, 2048, dtype=np.uint8)
    assert (np.asarray(gc.gear_hashes(data))
            == gc.gear_hashes_ref(data)).all()


def test_skipmin_allows_min_below_window():
    """Skip-min restarts the hash, so min_size < WINDOW is legal there
    (the default policy's WINDOW floor is about window-straddle
    equality, which skip-min does not rely on)."""
    rng = np.random.RandomState(6)
    data = rng.randint(0, 256, 5000, dtype=np.uint8).tobytes()
    want = gc.chunk_stream_skipmin_ref(data, 8, 6, 512)
    assert gc.chunk_stream_np(data, 8, 6, 512, cdc_policy=2) == want
    with pytest.raises(ValueError):
        gc.chunk_stream_np(data, 8, 6, 512)  # default policy still floors


def test_unknown_policy_rejected():
    with pytest.raises(ValueError):
        gc.chunk_stream(b"x" * 100, cdc_policy=3)
    with pytest.raises(ValueError):
        gc.chunk_stream_np(b"x" * 100, cdc_policy=0)
    from fastdfs_tpu.dedup.engine import DedupConfig, DedupEngine
    with pytest.raises(ValueError):
        DedupEngine(DedupConfig(cdc_policy=7))


def test_skipmin_skips_hash_work():
    """Semantic spot-check of WHY skip-min exists: a candidate planted
    strictly inside the skipped region must not produce a cut."""
    rng = np.random.RandomState(8)
    data = rng.randint(0, 256, 4096, dtype=np.uint8).tobytes()
    min_size, avg_bits, max_size = 512, 6, 4096
    cuts = gc.chunk_stream_skipmin_ref(data, min_size, avg_bits, max_size)
    last = 0
    for c in cuts[:-1]:
        assert c - last >= min_size
        last = c


# ---------------------------------------------------------------------------
# multi-chip fan-out
# ---------------------------------------------------------------------------

def _multi_device():
    import jax
    return len(jax.local_devices()) >= 8


@pytest.mark.skipif(not _multi_device(), reason="needs 8 (virtual) devices")
def test_fingerprint_step_bit_identical_across_mesh_sizes():
    import jax

    from fastdfs_tpu.ops.minhash import minhash_batch
    from fastdfs_tpu.parallel.ingest_step import (fingerprint_mesh,
                                                  make_fingerprint_step)

    rng = np.random.RandomState(3)
    N, L = 16, 256
    batch = np.zeros((N, L), dtype=np.uint8)
    lens = rng.randint(1, L + 1, N).astype(np.int32)
    for i in range(N):
        batch[i, :lens[i]] = rng.randint(0, 256, lens[i], dtype=np.uint8)
    want_d = np.zeros((N, 5), dtype=np.uint32)
    for i in range(N):
        want_d[i] = np.frombuffer(
            hashlib.sha1(batch[i, :lens[i]].tobytes()).digest(), dtype=">u4")
    want_s = np.asarray(minhash_batch(batch, lens, 16, 5))
    for n_dev in (1, 2, 8):
        step = make_fingerprint_step(fingerprint_mesh(n_dev),
                                     num_perms=16, shingle=5)
        d, s = step(batch, lens)
        assert (np.asarray(d) == want_d).all(), n_dev
        assert (np.asarray(s) == want_s).all(), n_dev
        jax.block_until_ready((d, s))


# row_tile 8 is one rung; 64 has the rungs (64, 8) once a tile's fixed
# cost is set to nothing (the shipped 1 MiB leaves these toy widths one).
@pytest.mark.skipif(not _multi_device(), reason="needs 8 (virtual) devices")
@pytest.mark.parametrize("row_tile", [8, 64])
def test_engine_fan_out_matches_single_device(monkeypatch, row_tile):
    from fastdfs_tpu.dedup import engine
    from fastdfs_tpu.dedup.engine import DedupConfig, DedupEngine

    monkeypatch.setattr(engine, "_TILE_FIXED_BYTES", 0)
    rng = np.random.RandomState(4)
    data = rng.randint(0, 256, 20000, dtype=np.uint8).tobytes()
    geo = dict(min_size=64, avg_bits=8, max_size=256, row_tile=row_tile,
               use_pallas=False)
    fan = DedupEngine(DedupConfig(fan_out=8, **geo))
    one = DedupEngine(DedupConfig(fan_out=1, **geo))
    spans_f, d_f, s_f = fan.fingerprint(data)
    spans_1, d_1, s_1 = one.fingerprint(data)
    assert spans_f == spans_1
    assert (d_f == d_1).all()
    assert (s_f == s_1).all()
    # The fan-out path places its tiles on the devices and counts them
    # by their rows, each device an eighth of every tile; the host path
    # places nothing.
    plan = engine.tile_plan([ln for _, ln in spans_f], 64, 256, row_tile)
    assert len({rows for rows, _, _ in plan}) == len(
        engine._row_ladder(row_tile, 256))
    assert fan.tiles_by_rows == {
        rung: sum(rows == rung for rows, _, _ in plan)
        for rung in engine._row_ladder(row_tile, 256)}
    assert fan.device_bytes == {dev: sum(rows * blen for rows, blen, _
                                         in plan) // 8 for dev in range(8)}
    assert (one.tiles_by_rows, one.device_bytes) == ({}, {})


def test_engine_rejects_indivisible_fan_out():
    from fastdfs_tpu.dedup.engine import DedupConfig, DedupEngine
    with pytest.raises(ValueError):
        DedupEngine(DedupConfig(row_tile=8, fan_out=3, use_pallas=False))


# ---------------------------------------------------------------------------
# staging_buffer growth audit
# ---------------------------------------------------------------------------

def test_chunk_stream_np_reuses_work_buffers():
    """Repeated host-path chunking at ANY large size must hold the
    staging pool fixed: the tiled scan keys its two uint32 work buffers
    by the constant tile span, never the input length."""
    rng = np.random.RandomState(12)
    sizes = [1 << 20, (1 << 21) + 777, 3 * (1 << 20) + 13, 1 << 22]
    data0 = rng.randint(0, 256, sizes[0], dtype=np.uint8).tobytes()
    gc.chunk_stream_np(data0, 256, 10, 4096)  # populate the pool
    before = gc.staging_buffer_stats()
    for n in sizes:
        data = rng.randint(0, 256, n, dtype=np.uint8).tobytes()
        for policy in (1, 2):
            gc.chunk_stream_np(data, 256, 10, 4096, cdc_policy=policy)
    after = gc.staging_buffer_stats()
    assert after == before, (before, after)
    # and the buffers really are the scan's fixed-span work pair
    span_keys = [k for k in after["keys"] if k[1] in gc._NP_WORK_SLOTS]
    assert len(span_keys) == 2
    assert all(k[0] == 4 * (gc._NP_TILE + gc._HALO) for k in span_keys)


def test_engine_two_slot_rotation_no_realloc():
    """The engine's double-buffered device staging must not realloc per
    call: a second fingerprint of a multi-tile stream adds zero buffers."""
    from fastdfs_tpu.dedup.engine import DedupConfig, DedupEngine

    rng = np.random.RandomState(13)
    eng = DedupEngine(DedupConfig(min_size=64, avg_bits=8, max_size=256,
                                  row_tile=8, use_pallas=False, fan_out=1))
    data = rng.randint(0, 256, 30000, dtype=np.uint8).tobytes()
    eng.fingerprint(data)  # populate every (size, slot) the shape needs
    before = gc.staging_buffer_stats()
    spans, d1, s1 = eng.fingerprint(data)
    after = gc.staging_buffer_stats()
    assert after == before, (before, after)
    assert len(spans) > eng.config.row_tile  # really was multi-tile
