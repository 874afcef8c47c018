"""Bit-exactness of the Pallas production kernels against their XLA
reference twins (SURVEY.md §7: 'keep a bit-exact CPU cross-check in
tests').

These run the kernels in Pallas interpret mode on the CPU mesh; on the
chip the compiled kernels are held to a hashlib / NumPy reference by
every benchmark run (``benchmark/run.py:compare``) and by
``chip_smoke.py``.  The production wiring is
``DedupEngine._fingerprint_batch``, which selects the Pallas path on
TPU and the XLA reference elsewhere.
"""

import hashlib

import numpy as np
import pytest

from fastdfs_tpu.ops.minhash import EMPTY, minhash_batch, survivor_segmin
from fastdfs_tpu.ops.pallas_minhash import (minhash_batch_pallas,
                                            survivor_segmin_pallas)
from fastdfs_tpu.ops.pallas_sha1 import launch_geometry, sha1_batch_pallas
from fastdfs_tpu.ops.sha1 import sha1_batch, sha1_hex


def _rand_batch(rng, n, L, degenerate=True):
    data = rng.randint(0, 256, size=(n, L), dtype=np.uint8)
    lens = rng.randint(1, L + 1, size=n).astype(np.int32)
    lens[0] = L
    if degenerate and n > 2:
        lens[1] = 3          # shorter than the shingle
        lens[2] = 1
    for i in range(n):
        data[i, lens[i]:] = 0
    return data, lens


@pytest.mark.parametrize("n,L", [(4, 2048), (3, 4096), (5, 6000),
                                 (2, 65536), (130, 512)])
def test_sha1_pallas_matches_hashlib(n, L):
    rng = np.random.RandomState(n * 1000 + L)
    data, lens = _rand_batch(rng, n, L)
    out = np.asarray(sha1_batch_pallas(data, lens, L, sub=1, interpret=True))
    for i in range(n):
        expect = hashlib.sha1(data[i, :lens[i]].tobytes()).hexdigest()
        assert sha1_hex(out[i]) == expect, i


# The row-major kernel (a tile under 128 rows) ends its launch at its
# longest row: (tile width, row lengths).  A width of 32,704 bytes is 512
# blocks, 64 grid steps of 8; 503 bytes pad to 512, exactly one step, and
# 504 to one block more.
ROW_MAJOR_CASES = {
    "one_row_of_3_blocks_in_64_groups": (32704, [150]),
    "longest_ends_on_a_group_edge": (32704, [503, 200, 64]),
    "longest_one_byte_over_a_group_edge": (32704, [504, 503, 1]),
    "empty_trailing_rows": (32704, [1000, 77, 0, 0, 0]),
    "every_row_empty_but_row_0": (32704, [300] + [0] * 7),
    "nine_rows_longest_last": (32704, [5, 0, 64, 55, 56, 119, 120, 1, 2000]),
    "longest_at_the_full_width": (4096, [4096, 17, 4095]),
}


@pytest.mark.parametrize("as_words", [False, True], ids=["bytes", "words"])
@pytest.mark.parametrize("case", sorted(ROW_MAJOR_CASES))
def test_sha1_row_major_launch_ends_at_its_longest_row(case, as_words):
    width, lens = ROW_MAJOR_CASES[case]
    rng = np.random.RandomState(len(case))
    data = np.zeros((len(lens), width), np.uint8)
    for i, ln in enumerate(lens):
        data[i, :ln] = rng.randint(0, 256, ln)
    arg = data.view(np.uint32) if as_words else data
    out = np.asarray(sha1_batch_pallas(arg, np.array(lens, np.int32), width,
                                       interpret=True))
    for i, ln in enumerate(lens):
        assert sha1_hex(out[i]) == hashlib.sha1(
            data[i, :ln].tobytes()).hexdigest(), i


def test_launch_geometry_walks_to_the_longest_row_under_128_rows():
    M = 1 << 20
    # the width's blocks, as before, where no longest row is given
    assert launch_geometry(8, 8 * M) == (128, 131080)
    # whole groups of 8 blocks: 503 bytes are one group, 504 two
    assert launch_geometry(8, 32704, 503) == (128, 8)
    assert launch_geometry(8, 32704, 504) == (128, 16)
    assert launch_geometry(8, 8 * M, 4299161) == (128, 67176)
    assert launch_geometry(32, 65536, 65536) == launch_geometry(32, 65536)
    # the lane-major kernel walks its width whatever the rows hold
    assert launch_geometry(256, 65536, 100) == (256, 1025)


def test_sha1_pallas_matches_xla_reference():
    rng = np.random.RandomState(7)
    data, lens = _rand_batch(rng, 6, 8192)
    ref = np.asarray(sha1_batch(data, lens))
    got = np.asarray(sha1_batch_pallas(data, lens, 8192, sub=1, interpret=True))
    assert np.array_equal(ref, got)


@pytest.mark.parametrize("n,L", [(4, 4096), (3, 8192), (5, 6000), (2, 65536)])
def test_survivor_segmin_pallas_bit_exact(n, L):
    rng = np.random.RandomState(n * 31 + L)
    data, lens = _rand_batch(rng, n, L)
    ref = np.asarray(survivor_segmin(data, lens))
    got = np.asarray(survivor_segmin_pallas(data, lens, interpret=True))
    assert np.array_equal(ref, got)
    # the sketch is non-trivial on random data at these sizes
    assert (ref != EMPTY).any()


def test_minhash_pallas_bit_exact_signatures():
    rng = np.random.RandomState(11)
    data, lens = _rand_batch(rng, 6, 16384)
    ref = np.asarray(minhash_batch(data, lens))
    got = np.asarray(minhash_batch_pallas(data, lens, interpret=True))
    assert np.array_equal(ref, got)


def test_minhash_pallas_adversarial_contents():
    # constant bytes, ramp, and all-zeros exercise the phase extraction
    # and the empty-signature path
    L = 4096
    rows = np.stack([
        np.zeros(L, np.uint8),
        np.full(L, 0xFF, np.uint8),
        (np.arange(L) % 256).astype(np.uint8),
        np.tile(np.frombuffer(b"abcdefgh", np.uint8), L // 8),
    ])
    lens = np.full(4, L, np.int32)
    ref = np.asarray(survivor_segmin(rows, lens))
    got = np.asarray(survivor_segmin_pallas(rows, lens, interpret=True))
    assert np.array_equal(ref, got)
    r2 = np.asarray(minhash_batch(rows, lens))
    g2 = np.asarray(minhash_batch_pallas(rows, lens, interpret=True))
    assert np.array_equal(r2, g2)


def test_engine_batch_dispatch_paths_agree():
    # the engine's two dispatch paths (pallas vs reference) produce the
    # same digests/signatures for the same batch
    from fastdfs_tpu.dedup.engine import DedupConfig, DedupEngine

    rng = np.random.RandomState(3)
    data, lens = _rand_batch(rng, 4, 4096)
    ref_engine = DedupEngine(DedupConfig(use_pallas=False))
    d_ref, s_ref = (np.asarray(x)
                    for x in ref_engine._fingerprint_batch(data, lens))
    d2 = np.asarray(sha1_batch_pallas(data, lens, 4096, sub=1, interpret=True))
    s2 = np.asarray(minhash_batch_pallas(data, lens, interpret=True))
    assert np.array_equal(d_ref, d2)
    assert np.array_equal(s_ref, s2)



@pytest.mark.parametrize("n_q", [1, 8])
def test_near_scan_pallas_nominates_the_blocks_its_xla_twin_does(n_q):
    from fastdfs_tpu.ops.pallas_near_scan import (BLOCK, LANES,
                                                  near_scan_pallas,
                                                  near_scan_xla)
    rng = np.random.RandomState(21)
    blocks = 3
    sigs = rng.randint(0, 2**32, (64, blocks * BLOCK), dtype=np.uint64
                       ).astype(np.uint32)
    queries = rng.randint(0, 2**32, (n_q, 64), dtype=np.uint64
                          ).astype(np.uint32)
    # plant: a whole band of query 0 in block 0 (first row) and block 2
    # (last row), three lanes of a band (no candidate) in block 1, and one
    # band per later query at a row of its own
    sigs[20:24, 0] = queries[0, 20:24]
    sigs[0:4, 3 * BLOCK - 1] = queries[0, 0:4]
    sigs[8:11, BLOCK + 77] = queries[0, 8:11]
    for q in range(1, n_q):
        sigs[4 * q:4 * q + 4, (q % blocks) * BLOCK + 1000 * q] = \
            queries[q, 4 * q:4 * q + 4]
    sigs_t = sigs.reshape(64, -1, LANES)
    want = np.asarray(near_scan_xla(sigs_t, queries))
    got = np.asarray(near_scan_pallas(sigs_t, queries, interpret=True))
    assert got.shape == (n_q, blocks) and got.dtype == np.bool_
    assert np.array_equal(got, want)
    assert want[0].tolist() == [True, False, True]
    for q in range(1, n_q):
        assert want[q].tolist() == [b == q % blocks for b in range(blocks)]
