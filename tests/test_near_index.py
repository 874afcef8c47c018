"""The near-duplicate index on the device (``dedup/near_index.py``), on the
CPU backend at small size: held to ``MinHashLSHIndex``, the host reference
that stays in the repo, on random and on planted rows, before and after a
growth of its capacity; ties, tombstones, the seeded base, snapshots that
never hold the base, one pass for many waiting queries, and a pass whose
nominated blocks outnumber what its one program ranks.
"""

from __future__ import annotations

import os
import sys
import threading
import time

import numpy as np
import pytest

from fastdfs_tpu.dedup import near_index
from fastdfs_tpu.dedup.index import MinHashLSHIndex
from fastdfs_tpu.dedup.near_index import (DeviceNearIndex, _Waiting,
                                          base_rows, min_count)
from fastdfs_tpu.dedup.spans import new_acc
from fastdfs_tpu.ops.minhash import EMPTY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
import reference_neardup  # noqa: E402

P = 64


def _sig(rng) -> np.ndarray:
    return rng.integers(0, 1 << 32, P, dtype=np.uint32)


def _variant(rng, sig: np.ndarray, lanes: int) -> np.ndarray:
    out = sig.copy()
    at = rng.choice(P, lanes, replace=False)
    out[at] = rng.integers(0, 1 << 32, lanes, dtype=np.uint32)
    return out


def _both():
    return MinHashLSHIndex(P, 16), DeviceNearIndex(P, 16)


def _plant(rng, host, dev, families: int, strangers: int, tag: str = ""):
    roots = []
    for f in range(families):
        root = _sig(rng)
        roots.append(root)
        for j, sig in enumerate([root] + [_variant(rng, root, k)
                                          for k in (4, 12, 24, 36, 44)]):
            for idx in (host, dev):
                idx.add(sig, f"{tag}f{f}/{j}")
    for i in range(strangers):
        sig = _sig(rng)
        for idx in (host, dev):
            idx.add(sig, f"{tag}r{i}")
    return roots


def test_device_index_answers_as_the_host_reference_on_random_and_planted_rows():
    rng = np.random.default_rng(11)
    host, dev = _both()
    roots = _plant(rng, host, dev, families=12, strangers=150)
    for top_k, thr in ((11, 0.5), (5, 0.5), (3, 0.25), (11, 0.9)):
        for root in roots:
            assert dev.query(root, top_k, thr) == host.query(root, top_k, thr)
    stranger = _sig(rng)
    assert dev.query(stranger, 11, 0.5) == host.query(stranger, 11, 0.5) == []
    assert len(dev) == len(host) == 12 * 6 + 150


def test_ties_are_ordered_older_row_first_in_both_indexes():
    rng = np.random.default_rng(12)
    host, dev = _both()
    root = _sig(rng)
    twin = _variant(rng, root, 8)
    # same signature under four refs, between other rows: equal scores
    for ref, sig in (("c", twin), ("x", _sig(rng)), ("a", twin), ("d", root),
                     ("y", _sig(rng)), ("b", twin), ("e", root)):
        host.add(sig, ref)
        dev.add(sig, ref)
    want = [("d", 1.0), ("e", 1.0), ("c", 0.875), ("a", 0.875), ("b", 0.875)]
    assert host.query(root, 11, 0.5) == want
    assert dev.query(root, 11, 0.5) == want
    assert dev.query(root, 3, 0.5) == host.query(root, 3, 0.5) == want[:3]


def test_a_removed_row_is_returned_by_no_later_query_and_keeps_no_top_k_slot():
    rng = np.random.default_rng(13)
    host, dev = _both()
    root = _sig(rng)
    for j in range(6):
        sig = _variant(rng, root, 2 * j)
        host.add(sig, f"g{j}")
        dev.add(sig, f"g{j}")
    for idx in (host, dev):
        assert idx.remove("g1") == 1 and idx.remove("g1") == 0
    got = dev.query(root, 3, 0.5)
    assert got == host.query(root, 3, 0.5)
    assert [r for r, _ in got] == ["g0", "g2", "g3"]
    assert dev.signature_of("g1") is None
    assert dev.stats()["near_removed"] == 1


def test_an_all_empty_signature_is_not_indexed_and_has_no_answer():
    dev = DeviceNearIndex(P, 16)
    empty = np.full(P, EMPTY, np.uint32)
    assert dev.add(empty, "nothing") == -1
    assert dev.signature_of("nothing") is None
    assert dev.query(empty, 11, 0.5) == [] and len(dev) == 0
    with pytest.raises(ValueError):
        dev.query(empty, near_index.MAX_TOP_K + 1, 0.5)


def test_answers_are_the_same_before_and_after_a_growth(monkeypatch):
    # one block of capacity to start from, so that a few rows fill it
    monkeypatch.setattr(near_index, "MIN_CAPACITY", near_index.BLOCK)
    rng = np.random.default_rng(14)
    host = MinHashLSHIndex(P, 16)
    # 29,100 rows and their eighth to spare: two blocks, 3,668 rows free
    dev = DeviceNearIndex(P, 16, base=(29_100, 5))
    dev.warmup()
    first = dev.capacity
    assert first == 2 * near_index.BLOCK
    roots = _plant(rng, host, dev, families=6, strangers=40)
    before = [dev.query(r, 11, 0.5) for r in roots]
    assert before == [host.query(r, 11, 0.5) for r in roots]
    assert dev.capacity == first
    more = _plant(rng, host, dev, families=620, strangers=0, tag="late/")
    assert dev.capacity == near_index.GROWTH * first
    assert [dev.query(r, 11, 0.5) for r in roots] == before
    assert all(dev.query(r, 11, 0.5) == host.query(r, 11, 0.5)
               for r in more[::150] + more[-2:])
    # the base came through the growth too
    row = dev.base_rows - 1
    assert dev.query(base_rows(5, row, row + 1)[0], 11, 0.5) == [
        (f"base/{row}", 1.0)]
    stats = dev.stats()
    assert stats["near_rows"] == dev.base_rows + len(dev)
    assert stats["near_resident_bytes"] == dev.capacity * (4 * P + 1)


def test_the_base_rows_on_the_device_equal_numpys_and_the_references():
    dev = DeviceNearIndex(P, 16, base=(40_000, 1997))
    dev.warmup()
    on_device = np.asarray(dev._sigs_t).reshape(P, -1).T
    want = base_rows(1997, 0, 40_000, P)
    assert np.array_equal(on_device[:40_000], want)
    assert not on_device[40_000:].any()
    assert np.array_equal(reference_neardup.base_rows(1997, 0, 40_000, P), want)
    assert np.array_equal(reference_neardup.base_rows(1997, 123, 456, P),
                          want[123:456])
    live = np.asarray(dev._live).reshape(-1)
    assert live[:40_000].all() and not live[40_000:].any()
    # warming up is not counted as being asked
    assert dev.stats()["near_scans"] == dev.stats()["near_queries"] == 0
    # a base row is found under its ref by rule, and can be tombstoned
    assert np.array_equal(dev.signature_of("base/77"), want[77])
    assert dev.query(want[77], 11, 0.5) == [("base/77", 1.0)]
    assert dev.signature_of("base/40000") is None
    assert dev.remove("base/77") == 1
    assert dev.query(want[77], 11, 0.5) == []
    assert dev.signature_of("base/77") is None


def test_own_rows_over_a_base_answer_as_the_reference_scanning_all_of_it():
    rng = np.random.default_rng(15)
    rows, seed = 30_000, 9
    dev = DeviceNearIndex(P, 16, base=(rows, seed))
    near = base_rows(seed, 2_000, 2_001)[0]      # a document like a base row
    refs, sigs = [], []
    for j, sig in enumerate([_variant(rng, near, 6), _variant(rng, near, 20),
                             _sig(rng)]):
        dev.add(sig, f"own/{j}")
        refs.append(f"own/{j}")
        sigs.append(sig)
    sources = lambda: list(reference_neardup.base_blocks(  # noqa: E731
        seed, rows, P, block=7_000)) + [(refs, np.array(sigs))]
    for query in sigs + [near]:
        want = reference_neardup.near_dups([query], sources(), 16, 0.5, 11)[0]
        assert dev.query(query, 11, 0.5) == want
    assert dev.query(sigs[0], 11, 0.5)[0][0] == "own/0"
    assert "base/2000" in [r for r, _ in dev.query(sigs[0], 11, 0.5)]


class _NotTheDevice:
    """Stands where the device arrays were: any use of it is an error."""

    def __getattr__(self, name):
        raise AssertionError(f"a snapshot touched the device array ({name})")


def test_a_snapshot_with_a_base_holds_the_own_rows_only_and_reloads(tmp_path):
    rng = np.random.default_rng(16)
    base = (40_000, 21)
    dev = DeviceNearIndex(P, 16, base=base)
    root = _sig(rng)
    for j in range(5):
        dev.add(_variant(rng, root, 3 * j), f"doc/{j}")
    dev.remove("doc/3")
    dev.remove("base/5")
    want = dev.query(root, 11, 0.5)
    kept, dev._sigs_t, dev._live = (dev._sigs_t, dev._live), \
        _NotTheDevice(), _NotTheDevice()
    path = str(tmp_path / "near.npz")
    dev.save(path)                  # reads nothing of the device
    dev._sigs_t, dev._live = kept
    assert os.path.getsize(path) < 16_000          # the base is 10 MB
    data = np.load(path, allow_pickle=True)
    assert data["sigs"].shape == (4, P) and len(data["refs"]) == 4
    assert int(data["base_rows"]) == 40_000 and int(data["base_seed"]) == 21

    again = DeviceNearIndex.load(path, base)
    assert again.query(root, 11, 0.5) == want
    assert len(again) == 4 and again.signature_of("doc/3") is None
    assert again.signature_of("base/5") is None
    assert np.array_equal(again.signature_of("doc/4"), dev.signature_of("doc/4"))
    for other in (None, (40_000, 22), (39_999, 21)):
        with pytest.raises(ValueError, match="written over the base"):
            DeviceNearIndex.load(path, other)
    # a snapshot of the host reference (no base in it) loads where no base runs
    host = MinHashLSHIndex(P, 16)
    host.add(root, "old")
    host.save(str(tmp_path / "host.npz"))
    assert DeviceNearIndex.load(str(tmp_path / "host.npz")).query(
        root, 11, 0.5) == [("old", 1.0)]
    with pytest.raises(ValueError, match="written over the base"):
        DeviceNearIndex.load(str(tmp_path / "host.npz"), base)


def test_eight_waiting_queries_share_passes_and_answer_as_eight_in_turn():
    rng = np.random.default_rng(17)
    host, dev = _both()
    roots = _plant(rng, host, dev, families=8, strangers=60)
    in_turn = [dev.query(r, 11, 0.5) for r in roots]
    assert in_turn == [host.query(r, 11, 0.5) for r in roots]
    before = dev.stats()
    assert before["near_scans"] == before["near_queries"] == 8

    got: list = [None] * 8
    # the scanner is held back until all eight wait, so they share passes:
    # what it took before the gate opens, and one pass for the rest
    gate = threading.Event()
    taken: list[int] = []
    real = dev._pass

    def held(batch, counted=True):
        taken.append(len(batch))
        gate.wait(30)
        return real(batch, counted)
    dev._pass = held

    def ask(i):
        got[i] = dev.query(roots[i], 11, 0.5)
    threads = [threading.Thread(target=ask, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 30
    while sum(taken[:1]) + len(dev._queue) < 8 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sum(taken[:1]) + len(dev._queue) == 8
    gate.set()
    for t in threads:
        t.join(60)
    assert not any(t.is_alive() for t in threads)
    assert got == in_turn
    after = dev.stats()
    assert after["near_queries"] - before["near_queries"] == 8
    assert sum(taken) == 8 and len(taken) == (1 if taken[0] == 8 else 2)
    assert after["near_scans"] - before["near_scans"] == len(taken) < 8
    assert after["near_inserts"] == len(dev) and after["near_scan_us"] > 0


def test_min_count_is_the_least_count_at_or_over_the_threshold():
    assert min_count(0.5, 64) == 32 and min_count(0.0, 64) == 0
    assert min_count(0.3, 64) == 20 and min_count(1.0, 64) == 64
    for thr in (0.1, 0.25, 0.33, 0.5, 0.75, 0.99):
        c = min_count(thr, 64)
        assert c / 64 >= thr > (c - 1) / 64


# -- more nominated blocks than the pass program ranks ---------------------------

BLOCK = near_index.BLOCK


def _spread_families(tmp_path, families: int = 6):
    """Both indexes over the same 65,436 rows (the four blocks of a fresh
    index's capacity), loaded into the device's from the host's snapshot:
    strangers, and families with member j in block j (members 0 and 3
    the same signature under two refs: a tie across blocks; 1 and 2 keep
    the first band, so every family nominates all four blocks)."""
    rng = np.random.default_rng(18)
    sigs = rng.integers(0, 1 << 32, (4 * BLOCK - 100, P), dtype=np.uint32)
    refs = [f"r{i}" for i in range(len(sigs))]
    roots = []
    for f in range(families):
        root = _sig(rng)
        roots.append(root)
        near = [root.copy(), root.copy()]
        near[0][8:20] ^= 1
        near[1][8:36] = _sig(rng)[8:36]
        for j, sig in enumerate((root, *near, root)):
            row = j * BLOCK + 300 * f + 17 * j
            sigs[row], refs[row] = sig, f"f{f}/{j}"
    host = MinHashLSHIndex(P, 16)
    for sig, ref in zip(sigs, refs):
        host.add(sig, ref)
    path = str(tmp_path / "spread.npz")
    host.save(path)
    return host, DeviceNearIndex.load(path), roots


@pytest.fixture
def rank_blocks(monkeypatch):
    """Sets ``RANK_BLOCKS`` for the programs made after it (the cache of
    ``_programs`` is cleared before and after)."""
    def set_to(n: int) -> None:
        monkeypatch.setattr(near_index, "RANK_BLOCKS", n)
        near_index._programs.cache_clear()
    yield set_to
    near_index._programs.cache_clear()


def _counting(dev: DeviceNearIndex) -> dict:
    """Counts the calls of the index's pass and rank programs."""
    calls = {"scan": 0, "rank": 0}

    def wrap(name, program):
        def counted(*args):
            calls[name] += 1
            return program(*args)
        return counted
    dev._programs = {**dev._programs,
                     **{k: wrap(k, dev._programs[k]) for k in calls}}
    return calls


@pytest.mark.parametrize("ranked", [1, 3])
def test_a_pass_that_nominates_more_blocks_than_it_ranks_spills_exactly(
        tmp_path, rank_blocks, ranked):
    rank_blocks(ranked)          # the pass program ranks 1 or 3 of the 4
    host, dev, roots = _spread_families(tmp_path)
    calls = _counting(dev)
    for top_k, thr in ((11, 0.5), (3, 0.25), (1, 0.9)):
        for root in roots[:3]:
            assert dev.query(root, top_k, thr) == host.query(root, top_k, thr)
    want = [("f0/0", 1.0), ("f0/3", 1.0)]        # the tie: older row first
    assert dev.query(roots[0], 2, 0.9) == want
    assert dev.stats()["near_rank_spills"] == dev.stats()["near_scans"] == 10
    # spilled blocks beyond the first RANK_BLOCKS: 4 - ranked, by the
    # rank program RANK_BLOCKS at a time
    assert calls == {"scan": 10, "rank": 10 * -(-(4 - ranked) // ranked)}
    # one pass for every family at once: one spill
    batch = [_Waiting(r, 11, min_count(0.5, P), new_acc()) for r in roots]
    dev._pass(batch)
    assert [w.result for w in batch] == [host.query(r, 11, 0.5)
                                         for r in roots]
    assert dev.stats()["near_rank_spills"] == 11
    # a query whose blocks the program ranks alone does not spill
    assert dev.query(_sig(np.random.default_rng(19)), 11, 0.5) == []
    assert dev.stats()["near_rank_spills"] == 11


def test_a_pass_that_nominates_no_more_than_it_ranks_is_one_program(tmp_path):
    host, dev, roots = _spread_families(tmp_path)    # 4 blocks of 8
    calls = _counting(dev)
    for root in roots:
        assert dev.query(root, 11, 0.5) == host.query(root, 11, 0.5)
    batch = [_Waiting(r, 11, min_count(0.25, P), new_acc()) for r in roots]
    dev._pass(batch)
    assert [w.result for w in batch] == [host.query(r, 11, 0.25)
                                         for r in roots]
    assert calls == {"scan": len(roots) + 1, "rank": 0}
    stats = dev.stats()
    assert stats["near_rank_spills"] == 0
    assert stats["near_scans"] == len(roots) + 1
