"""``near_dups`` through the normal path (client -> daemon -> dio worker ->
sidecar -> the index on the device), on the CPU at small size, held to the
plain reference (``benchmark/reference_neardup.py`` over
``benchmark/reference.py``'s NumPy MinHash) scanning the whole base and
the node's own rows: families of three generations, a deleted generation,
ties at equal score, a file whose signature is all ``EMPTY`` (ENODATA), a
query sent right after its upload's acknowledgement, and eight queries at
once.  One sidecar (``--platform cpu --near-base 30000:7``) behind one
storage daemon for the whole module.
"""

from __future__ import annotations

import json
import os
import sys
import threading

import numpy as np
import pytest

from fastdfs_tpu.client import FdfsClient
from fastdfs_tpu.common.protocol import StorageCmd
from fastdfs_tpu.sidecar import rpc
from harness import REPO, Sidecar, start_storage, start_tracker, upload_retry

sys.path.insert(0, os.path.join(REPO, "benchmark"))
import reference  # noqa: E402
import reference_neardup  # noqa: E402
from generators import versions  # noqa: E402

BASE_ROWS, BASE_SEED = 30_000, 7
HB = "heart_beat_interval = 1\nstat_report_interval = 1"


def _widths() -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           "crawl_neardup.json")) as fh:
        return json.load(fh)["widths"]


class _Node:
    """The cluster, and what the reference knows of it: every file this
    test stored, in the order it was acknowledged."""

    def __init__(self, base: str):
        self.sidecar = Sidecar(os.path.join(base, "sc"), (
            "--platform", "cpu", "--near-base", f"{BASE_ROWS}:{BASE_SEED}"),
            state_dir=os.path.join(base, "state"))
        self.tr = start_tracker(os.path.join(base, "tr"))
        self.st = start_storage(
            os.path.join(base, "st"), trackers=[f"127.0.0.1:{self.tr.port}"],
            dedup_mode="sidecar", dedup_sidecar=self.sidecar.sock, extra=HB)
        self.cli = FdfsClient([f"127.0.0.1:{self.tr.port}"])
        self.widths = _widths()
        self.refs: list[str | None] = []       # None: deleted since
        self.sigs: list[np.ndarray] = []

    def store(self, data: bytes) -> str:
        fid = upload_retry(self.cli, data, ext="bin")
        sig = reference.file_signature(data, self.widths)
        if (sig != reference.EMPTY).any():      # an empty one is not indexed
            self.refs.append(fid)
            self.sigs.append(sig)
        return fid

    def delete(self, fid: str) -> None:
        self.cli.delete_file(fid)
        self.refs[self.refs.index(fid)] = None

    def want(self, fid: str) -> list[tuple[str, str]]:
        """The reference's reply: all 30,000 base rows in blocks, then
        the rows this test stored (a deleted one is no row)."""
        alive = [i for i, r in enumerate(self.refs) if r is not None]
        own = ([self.refs[i] for i in alive],
               np.array([self.sigs[i] for i in alive]))
        ranked = reference_neardup.near_dups(
            [self.sigs[self.refs.index(fid)]],
            list(reference_neardup.base_blocks(BASE_SEED, BASE_ROWS, 64,
                                               block=8_192)) + [own],
            bands=16, threshold=0.5, top_k=11)[0]
        return reference_neardup.reply_lines(fid, ranked, top_k=5)

    def got(self, fid: str, cli: FdfsClient | None = None):
        return [(ref, f"{score:.4f}")
                for ref, score in (cli or self.cli).near_dups(fid)]

    def stop(self) -> None:
        self.cli.close()
        self.st.stop()
        self.tr.stop()
        self.sidecar.stop()


@pytest.fixture(scope="module")
def node(tmp_path_factory):
    n = _Node(str(tmp_path_factory.mktemp("near_served")))
    try:
        yield n
    finally:
        n.stop()


def _family(seed: int, size: int, generations: int = 3) -> list[bytes]:
    """Generation 0 fresh, each next one edited as the benchmark's
    ``revisits`` edits (8 edits a MiB of 256-4,096 B)."""
    params = {"sizes_mib": [size / (1 << 20)], "edits_per_mib": 8,
              "edit_min": 256, "edit_max": 4096}
    gen = versions.Generator(params, seed, 0, 1)
    out = [gen._make(b"", 0)]
    for g in range(1, generations):
        out.append(gen._make(out[-1], g))
    return out


def test_families_of_three_generations_answer_as_the_reference(node):
    stats0 = node.sidecar.stats()
    assert stats0["near_base_rows"] == BASE_ROWS
    assert stats0["near_rows"] == BASE_ROWS + len(node.refs)
    families = [[node.store(data) for data in _family(100 + f, size)]
                for f, size in enumerate((204_800, 1_048_576, 204_800))]
    for fam in families:
        for g, fid in enumerate(fam):
            got = node.got(fid)
            assert got == node.want(fid)
            # the whole family and nothing else, the best first
            assert {ref for ref, _ in got} == set(fam) - {fid}
            assert all(float(score) >= 0.5 for _, score in got)
    stats = node.sidecar.stats()
    assert stats["near_inserts"] - stats0["near_inserts"] == 9
    assert stats["near_queries"] - stats0["near_queries"] == 9
    assert stats["near_rows"] == BASE_ROWS + len(node.refs)
    assert stats["near_resident_bytes"] >= BASE_ROWS * 256
    for name in ("insert", "queue_wait", "scan", "rank"):
        assert stats["span_n"][f"fdfs.near.{name}"] >= 9     # tracing off


def test_a_query_right_after_its_uploads_acknowledgement_finds_it(node):
    gens = _family(200, 204_800, 2)
    first = node.store(gens[0])
    for _ in range(3):           # each later copy is acknowledged, then asked
        fid = node.store(gens[1])
        got = node.got(fid)
        assert got == node.want(fid)
        assert first in [ref for ref, _ in got]


def test_equal_scores_come_back_older_file_first(node):
    gens = _family(300, 204_800, 2)
    root = node.store(gens[0])
    twins = [node.store(gens[1]) for _ in range(3)]    # one signature, thrice
    got = node.got(root)
    assert got == node.want(root)
    assert [ref for ref, _ in got] == twins            # in upload order
    assert len({score for _, score in got}) == 1
    got = node.got(twins[1])
    assert got == node.want(twins[1])
    assert [ref for ref, _ in got][:2] == [twins[0], twins[2]]   # both 1.0


def test_a_deleted_generation_is_returned_by_no_later_query(node):
    fam = [node.store(data) for data in _family(400, 204_800)]
    assert fam[1] in [ref for ref, _ in node.got(fam[2])]
    node.delete(fam[1])
    for fid in (fam[0], fam[2]):
        got = node.got(fid)
        assert got == node.want(fid)
        assert fam[1] not in [ref for ref, _ in got]
    status, _ = rpc(node.sidecar.sock, StorageCmd.DEDUP_NEARDUPS,
                    fam[1].encode())
    assert status == 61
    assert node.sidecar.stats()["near_removed"] >= 1


def test_a_file_with_an_all_empty_signature_is_enodata(node):
    # No shingle hash of a constant byte 1 has a zero low byte, so no
    # survivor: the reference signs it all EMPTY, and so does the engine.
    blank = b"\x01" * 150_000
    assert (reference.file_signature(blank, node.widths)
            == reference.EMPTY).all()
    before = len(node.refs)
    fid = node.store(blank)
    assert len(node.refs) == before                  # not a row
    status, body = rpc(node.sidecar.sock, StorageCmd.DEDUP_NEARDUPS,
                       fid.encode())
    assert (status, body) == (61, b"")
    assert node.cli.near_dups(fid) == []             # the client's reading


def test_eight_queries_at_once_answer_as_eight_in_turn_in_fewer_passes(node):
    fams = [[node.store(data) for data in _family(500 + f, 204_800, 2)]
            for f in range(8)]
    asked = [fam[1] for fam in fams]
    in_turn = [node.got(fid) for fid in asked]
    assert in_turn == [node.want(fid) for fid in asked]
    clients = [FdfsClient([f"127.0.0.1:{node.tr.port}"]) for _ in asked]
    before = node.sidecar.stats()
    got: list = [None] * 8

    def ask(i):
        for _ in range(4):
            got[i] = node.got(asked[i], clients[i])
    threads = [threading.Thread(target=ask, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    for c in clients:
        c.close()
    assert not any(t.is_alive() for t in threads)
    assert got == in_turn
    after = node.sidecar.stats()
    queries = after["near_queries"] - before["near_queries"]
    scans = after["near_scans"] - before["near_scans"]
    assert queries == 32 and 4 <= scans < queries
    assert after["near_scan_us"] > before["near_scan_us"]


def test_the_snapshot_holds_the_own_rows_and_is_refused_under_another_base(
        node, tmp_path):
    """Last in the module: it stops the node's sidecar."""
    held = sum(r is not None for r in node.refs)
    node.sidecar.stop()                              # SIGTERM: state written
    near = os.path.join(os.path.dirname(node.sidecar.log_path), "..", "state",
                        "sidecar_near.npz")
    assert os.path.getsize(near) < held * 256 + 65_536     # the base: 7.7 MB
    data = np.load(near, allow_pickle=True)
    assert len(data["refs"]) == held
    stored = {json.loads(str(r)): s for r, s in zip(data["refs"],
                                                    data["sigs"])}
    for ref, sig in zip(node.refs, node.sigs):
        if ref is not None:
            assert np.array_equal(stored[ref], sig)

    from fastdfs_tpu.dedup.engine import DedupConfig
    from fastdfs_tpu.sidecar import DedupSidecar
    state = os.path.dirname(near)
    same = DedupSidecar(str(tmp_path / "a.sock"), state_dir=state,
                        config=DedupConfig(near_base=(BASE_ROWS, BASE_SEED)))
    assert len(same.engine.near) == held
    fid = next(r for r in node.refs if r is not None)
    assert same._neardups(fid.encode())[0] == 0
    other = DedupSidecar(str(tmp_path / "b.sock"), state_dir=state,
                         config=DedupConfig(near_base=(BASE_ROWS, 8)))
    assert len(other.engine.near) == 0 and len(other.engine.exact) > 0
    assert other._neardups(fid.encode())[0] == 61
