"""ExactDigestIndex internals: the paths that guard every dedup verdict.

The columnar sorted-base + delta layout (fastdfs_tpu/dedup/index.py) was
engineered for tens of millions of entries; these tests drive the parts
test-scale usage never reaches: the delta→base merge at the real 65,536
threshold, tombstone compaction, delta-shadowing-base lookups at the
boundary, the v1→v2 snapshot migration, and carrier-column pruning.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pytest

from fastdfs_tpu.dedup.index import ExactDigestIndex, MinHashLSHIndex


def _digests(n: int, seed: int = 0) -> list[bytes]:
    """n distinct 20-byte digests (sha1 of counters — realistic keys)."""
    return [hashlib.sha1(f"{seed}:{i}".encode()).digest() for i in range(n)]


# ---------------------------------------------------------------------------
# delta→base merge at the production threshold
# ---------------------------------------------------------------------------

def test_merge_triggers_at_real_threshold_and_preserves_lookups():
    idx = ExactDigestIndex()
    n = 65536 + 500  # crosses max(65536, base/4) with an empty base
    digs = _digests(n)
    for i, d in enumerate(digs):
        assert idx.insert(d, [f"f{i % 97}", i])
    # the merge must actually have happened (delta folded into the base)
    assert len(idx._base) >= 65536
    assert idx._delta_rows < 65536
    assert len(idx) == n
    # spot-check lookups across both sides of the merge boundary
    for i in (0, 1, 65535, 65536, n - 1, n // 2):
        assert idx.lookup(digs[i]) == [f"f{i % 97}", i]
    # batch lookup agrees with scalar lookup
    sample = [digs[i] for i in range(0, n, 4096)]
    assert idx.lookup_batch(sample) == [idx.lookup(d) for d in sample]
    # no duplicate insertions slipped through
    assert not idx.insert(digs[123], ["other", 0])
    assert idx.lookup(digs[123]) == ["f" + str(123 % 97), 123]


def test_merge_compacts_tombstones():
    idx = ExactDigestIndex()
    digs = _digests(1000)
    for i, d in enumerate(digs):
        idx.insert(d, ["carrier", i])
    idx._merge()  # all in base
    for d in digs[::3]:
        assert idx.remove(d)
    assert int(idx._base.dead.sum()) == len(digs[::3])
    idx._merge()
    assert not idx._base.dead.any()
    assert len(idx._base) == 1000 - len(digs[::3])
    for i, d in enumerate(digs):
        if i % 3 == 0:
            assert idx.lookup(d) is None
        else:
            assert idx.lookup(d) == ["carrier", i]


def test_removed_digest_can_be_reinserted_with_new_ref():
    # delta shadows a tombstoned base row: the dedup engine re-attributes
    # a chunk after its first carrier was deleted.
    idx = ExactDigestIndex()
    digs = _digests(100)
    for i, d in enumerate(digs):
        idx.insert(d, ["old", i])
    idx._merge()
    assert idx.remove(digs[50])
    assert idx.insert(digs[50], ["new", 7])
    assert idx.lookup(digs[50]) == ["new", 7]
    # batch path must prefer the delta entry over the dead base row
    assert idx.lookup_batch([digs[50], digs[51]]) == [["new", 7],
                                                      ["old", 51]]
    # and the state survives a merge
    idx._merge()
    assert idx.lookup(digs[50]) == ["new", 7]
    assert len(idx) == 100


# ---------------------------------------------------------------------------
# insert_batch: one probe a commit, the answers of insert() digest by digest
# ---------------------------------------------------------------------------

class _FirstWriterWins:
    """The referee: a dict with ``insert``'s rule (a live digest keeps its
    ref) and ``remove``'s."""

    def __init__(self) -> None:
        self.refs: dict[bytes, list] = {}

    def insert_batch(self, digs, carrier, offsets) -> int:
        new = 0
        for d, off in zip(digs, offsets):
            if d not in self.refs:
                self.refs[d] = [carrier, int(off)]
                new += 1
        return new


def _random_digests(rng, n: int) -> list[bytes]:
    return [rng.bytes(20) for _ in range(n)]


def _nul_and_prefix_digests(rng, n: int) -> list[bytes]:
    """Digests ending in 0x00 (the S20 NUL rule) and digests that share
    their first 8 bytes (the integer key ties)."""
    out = []
    for i in range(n):
        d = bytearray(rng.bytes(20))
        if i % 3 == 0:
            d[-(1 + i % 12):] = bytes(1 + i % 12)
        if i % 3 == 1:
            d[:8] = b"\x07" * 8
        out.append(bytes(d))
    return out


def _scenario(case: str, rng) -> list[tuple]:
    """Operations: ("batch", digests, carrier, offsets), ("remove",
    digest), ("merge",)."""
    def batch(digs, carrier):
        return ("batch", digs, carrier, list(rng.integers(0, 1 << 40,
                                                          len(digs))))
    if case == "random":
        pool = _random_digests(rng, 6000)
        return [batch([pool[j] for j in rng.integers(0, len(pool), k)],
                      f"f{i}") for i, k in enumerate((1, 1280, 3000, 17,
                                                      2500))]
    if case == "duplicates_within":
        d = _random_digests(rng, 300)
        return [batch(d + d[::-1] + d[:50], "a"), batch(d[100:] * 3, "b")]
    if case == "base_and_delta":
        d = _random_digests(rng, 4000)
        return [batch(d[:2000], "base"), ("merge",), batch(d[2000:2500], "delta"),
                batch(d[1500:2100] + d[2400:3000] + d[:10], "mixed")]
    if case == "tombstones":
        d = _random_digests(rng, 1000)
        return ([batch(d[:800], "old"), ("merge",)]
                + [("remove", x) for x in d[::7]]
                + [batch(d[900:950], "delta")]
                + [("remove", x) for x in d[900:950:5]]
                + [batch(d, "new")])
    if case == "nul_tails_and_shared_prefixes":
        d = _nul_and_prefix_digests(rng, 900)
        return [batch(d[:400], "a"), ("merge",), batch(d[300:700], "b"),
                ("remove", d[0]), ("remove", d[301]), batch(d, "c")]
    if case == "small_batches_over_runs":
        # batches under _ONE_BY_ONE, one digest at a time, over runs that
        # hold shared prefixes, NUL tails and tombstones
        d = _nul_and_prefix_digests(rng, 3000)
        ops = [batch(d[:1000], "base"), ("merge",), batch(d[1000:1600], "run")]
        ops += [("remove", x) for x in d[::11]]
        for i, k in enumerate((1, 17, 200, 255, 3, 90)):
            pick = [d[j] for j in rng.integers(0, 2400, k)]
            ops.append(batch(pick + pick[:k // 3] + d[2400 + 100 * i:
                                                     2400 + 100 * i + 5],
                             f"s{i}"))
        return ops + [("remove", d[2401]), batch(d[2300:2700], "big")]
    if case == "empty":
        d = _random_digests(rng, 50)
        return [batch([], "none"), batch(d, "a"), batch([], "none")]
    if case == "sets_off_a_merge":
        d = _random_digests(rng, 70_000)
        return [batch(d[:1000], "a"), batch(d[500:], "b")]
    raise AssertionError(case)


INSERT_CASES = ["random", "duplicates_within", "base_and_delta",
                "tombstones", "nul_tails_and_shared_prefixes", "empty",
                "sets_off_a_merge", "snapshot_v2", "small_batches_over_runs"]


@pytest.mark.parametrize("case", INSERT_CASES)
def test_insert_batch_equals_sequential_inserts(case, tmp_path):
    rng = np.random.default_rng(INSERT_CASES.index(case))
    ops = _scenario("tombstones" if case == "snapshot_v2" else case, rng)
    batched, ref = ExactDigestIndex(), _FirstWriterWins()
    # insert() one digest at a time beside it (a merge's worth of them is
    # left to the referee alone: 65,536 single inserts take seconds)
    one_by_one = None if case == "sets_off_a_merge" else ExactDigestIndex()
    for op in ops:
        if op[0] == "batch":
            _, digs, carrier, offs = op
            got = batched.insert_batch(b"".join(digs), carrier,
                                       np.array(offs, dtype=np.int64))
            assert got == ref.insert_batch(digs, carrier, offs)
            if one_by_one is not None:
                assert got == sum(one_by_one.insert(d, [carrier, int(o)])
                                  for d, o in zip(digs, offs))
        elif op[0] == "remove":
            assert batched.remove(op[1]) == (ref.refs.pop(op[1], None)
                                             is not None)
            if one_by_one is not None:
                one_by_one.remove(op[1])
        else:
            batched._merge()
    if case == "sets_off_a_merge":
        assert batched.merges == 1 and len(batched._base) >= 65536
    if case == "snapshot_v2":
        batched.save(str(tmp_path / "exact"))
        batched = ExactDigestIndex.load(str(tmp_path / "exact"))
    assert batched.insert_batches == (0 if case == "snapshot_v2" else
                                      sum(op[0] == "batch" for op in ops))
    for idx in (batched, one_by_one):
        if idx is None:
            continue
        assert dict(idx.items()) == ref.refs
        assert len(idx) == len(ref.refs)
        seen = [d for op in ops if op[0] != "merge"
                for d in (op[1] if op[0] == "batch" else [op[1]])]
        probe = seen[::3] + _random_digests(rng, 20)
        want = [ref.refs.get(d) for d in probe]
        assert idx.lookup_batch(probe) == want
        assert [idx.lookup(d) for d in probe[:200]] == want[:200]


# ---------------------------------------------------------------------------
# snapshot formats
# ---------------------------------------------------------------------------

def test_v1_snapshot_migrates(tmp_path):
    # v1 layout: flat digest bytes + per-entry json refs, no exact_spec
    # marker (round-2 sidecars wrote these; load() must keep reading them).
    import json

    digs = _digests(257)
    refs = [json.dumps([f"file{i}", i * 10]) for i in range(len(digs))]
    p = str(tmp_path / "exact_v1.npz")
    np.savez(p, digests=np.frombuffer(b"".join(digs), dtype=np.uint8),
             refs=np.array(refs, dtype=object))
    idx = ExactDigestIndex.load(p)
    assert len(idx) == len(digs)
    for i, d in enumerate(digs):
        assert idx.lookup(d) == [f"file{i}", i * 10]


def test_v2_snapshot_roundtrip_with_tombstones_and_delta(tmp_path):
    idx = ExactDigestIndex()
    digs = _digests(3000)
    for i, d in enumerate(digs[:2000]):
        idx.insert(d, ["a", i])
    idx._merge()
    for d in digs[:100]:
        idx.remove(d)
    for i, d in enumerate(digs[2000:]):  # fresh delta on top
        idx.insert(d, ["b", i])
    p = str(tmp_path / "exact_v2")
    idx.save(p)
    idx2 = ExactDigestIndex.load(p)
    assert len(idx2) == len(idx)
    assert idx2.lookup(digs[0]) is None
    assert idx2.lookup(digs[150]) == ["a", 150]
    assert idx2.lookup(digs[2500]) == ["b", 500]


def test_items_pads_nul_terminated_digests():
    # numpy S20 strips trailing NULs on extraction; items() must re-pad
    # (~1/256 SHA1 digests end in 0x00 — silently shortened keys would
    # miss byte-equality consumers).
    idx = ExactDigestIndex()
    d_nul = b"\x01" * 19 + b"\x00"
    d_mid = b"\x02" * 10 + b"\x00" * 10
    idx.insert(d_nul, ["x", 1])
    idx.insert(d_mid, ["y", 2])
    idx._merge()  # move into the base (the S20 column)
    got = dict(idx.items())
    assert d_nul in got and got[d_nul] == ["x", 1]
    assert d_mid in got and got[d_mid] == ["y", 2]
    assert all(len(k) == 20 for k in got)


# ---------------------------------------------------------------------------
# carrier-column pruning (forget path)
# ---------------------------------------------------------------------------

def test_remove_by_carrier_spans_delta_and_base():
    idx = ExactDigestIndex()
    digs = _digests(300)
    for i, d in enumerate(digs[:200]):
        idx.insert(d, ["gone" if i % 2 else "kept", i])
    idx._merge()
    for i, d in enumerate(digs[200:]):
        idx.insert(d, ["gone" if i % 2 else "kept", 200 + i])
    n_gone = sum(1 for i in range(200) if i % 2) + \
        sum(1 for i in range(100) if i % 2)
    assert idx.remove_by_carrier("gone") == n_gone
    assert len(idx) == 300 - n_gone
    assert idx.remove_by_carrier("gone") == 0      # idempotent
    assert idx.remove_by_carrier("never-seen") == 0
    for i, d in enumerate(digs[:200]):
        assert (idx.lookup(d) is None) == bool(i % 2)
    # survivors intact through a subsequent compaction
    idx._merge()
    assert idx.lookup(digs[0]) == ["kept", 0]
    assert len(idx) == 300 - n_gone


def test_carrier_churn_does_not_leak_interned_ids(tmp_path):
    # create/forget cycles: forgotten file-id strings must leave the
    # carrier table (and its snapshots), not accumulate forever.
    idx = ExactDigestIndex()
    for round_ in range(50):
        digs = _digests(20, seed=round_)
        for i, d in enumerate(digs):
            idx.insert(d, [f"churn{round_}", i])
        assert idx.remove_by_carrier(f"churn{round_}") == 20
    idx.insert(_digests(1, seed=999)[0], ["survivor", 0])
    idx._merge()
    assert idx._carriers == ["survivor"]
    assert len(idx) == 1
    # snapshots carry only the live carrier
    p = str(tmp_path / "churn")
    idx.save(p)
    idx2 = ExactDigestIndex.load(p)
    assert idx2._carriers == ["survivor"]
    assert idx2.lookup(_digests(1, seed=999)[0]) == ["survivor", 0]


# ---------------------------------------------------------------------------
# LSH remove via the ref map (no linear scan)
# ---------------------------------------------------------------------------

def test_lsh_remove_tombstones_all_items_of_ref():
    rng = np.random.RandomState(9)
    idx = MinHashLSHIndex(64, 16)
    sigs = rng.randint(1, 2**32, (6, 64)).astype(np.uint32)
    for k in range(4):
        idx.add(sigs[k], "dup-file")
    idx.add(sigs[4], "other")
    assert idx.remove("dup-file") == 4
    assert idx.remove("dup-file") == 0
    assert idx.signature_of("dup-file") is None
    assert idx.signature_of("other") is not None
    # tombstoned items never surface in queries
    got = idx.query(sigs[0], top_k=10, min_similarity=0.0)
    assert all(ref != "dup-file" for ref, _ in got)
    # re-adding after removal works and signature_of tracks the latest
    idx.add(sigs[5], "dup-file")
    assert (idx.signature_of("dup-file") == sigs[5]).all()


def test_lsh_query_orders_ties_older_row_first_and_ranks_live_rows_only():
    """The rule both near indexes answer by: score descending, ties in the
    order the rows were added; a tombstone takes no place among the top."""
    rng = np.random.RandomState(10)
    idx = MinHashLSHIndex(64, 16)
    root = rng.randint(1, 2**32, 64).astype(np.uint32)
    half = root.copy()
    half[32:] = rng.randint(1, 2**32, 32).astype(np.uint32)
    for ref, sig in (("h9", half), ("full-b", root), ("h2", half),
                     ("gone", root), ("h5", half), ("full-a", root)):
        idx.add(sig, ref)
    idx.remove("gone")
    want = [("full-b", 1.0), ("full-a", 1.0), ("h9", 0.5), ("h2", 0.5),
            ("h5", 0.5)]
    for _ in range(3):          # not a set's iteration order: every time
        assert idx.query(root, top_k=10, min_similarity=0.5) == want
    assert idx.query(root, top_k=3, min_similarity=0.5) == want[:3]


def test_lsh_remove_roundtrips_through_snapshot(tmp_path):
    rng = np.random.RandomState(10)
    idx = MinHashLSHIndex(64, 16)
    s1 = rng.randint(1, 2**32, 64).astype(np.uint32)
    s2 = rng.randint(1, 2**32, 64).astype(np.uint32)
    idx.add(s1, "a")
    idx.add(s2, "b")
    idx.remove("a")
    p = str(tmp_path / "lsh")
    idx.save(p)
    idx2 = MinHashLSHIndex.load(p)
    assert idx2.signature_of("a") is None
    assert (idx2.signature_of("b") == s2).all()
    assert idx2.remove("b") == 1


def test_lsh_churn_compacts_tombstones():
    # Sustained create/delete churn must not grow signature rows or band
    # buckets without bound: once tombstones dominate, the index
    # compacts and queries/signature_of still work.
    rng = np.random.RandomState(12)
    idx = MinHashLSHIndex(64, 16)
    keep_sig = rng.randint(1, 2**32, 64).astype(np.uint32)
    idx.add(keep_sig, "keeper")
    for round_ in range(6):
        refs = [f"churn{round_}:{i}" for i in range(600)]
        for r in refs:
            idx.add(rng.randint(1, 2**32, 64).astype(np.uint32), r)
        for r in refs:
            assert idx.remove(r) == 1
    # rows bounded: far below the 3600 churned items
    assert len(idx._rows) < 1300, len(idx._rows)
    assert idx._dead < 1200
    assert (idx.signature_of("keeper") == keep_sig).all()
    got = idx.query(keep_sig, top_k=3, min_similarity=0.9)
    assert got and got[0][0] == "keeper"
    # bucket lists hold no dangling ids after compaction
    n = len(idx._rows)
    for b in idx._buckets:
        for ids in b.values():
            assert all(0 <= i < n for i in ids)
