"""The negotiated upload's client cuts as its node cuts.

* the client's fingerprints at a node's parameters (widths, policy and
  segment length) are the plain reference's cuts, segment by segment;
* a live node states its parameters (``QUERY_CHUNKING``) and a client
  upload at non-default widths stores the recipe the node would have cut;
* live cluster with the sidecar on the CPU: generation 0 plain,
  generation 1 negotiated across a segment boundary: stored recipe,
  shipped chunks and bytes, signature and read-back all equal the plain
  references (``benchmark/reference.py``, ``reference_negotiated.py``),
  the stages are in the access log, and a recipe cut under other
  parameters is refused by the node and ends in a counted plain upload;
* a client that cannot learn its node's parameters uploads plain;
* a shipped chunk whose bytes are not its digest is refused and nothing
  is stored under that digest;
* the commit assembles each segment once in the worker's buffer: a file
  over three segments commits to the references' recipe, CRC and
  signature with its present chunks read many to a ``preadv`` and nothing
  left under ``tmp/``; a recipe entry across a segment end, a present
  chunk of another stored length, a chunk gone before the commit all
  fail it with every reference given back; a digest repeated in one
  recipe reads right at every occurrence.

Small widths and a 1 MB segment keep it fast.
"""

import hashlib
import json
import os
import sys
import time
import zlib

import numpy as np
import pytest

from fastdfs_tpu.client import FdfsClient, StorageClient
from fastdfs_tpu.client.conn import StatusError
from fastdfs_tpu.client.fingerprint import (SHIPPED_PARAMS, ChunkingParams,
                                            fingerprint_buffer)
from fastdfs_tpu.client.storage_client import (pack_upload_chunks_prefix,
                                               pack_upload_recipe,
                                               unpack_upload_recipe_resp)
from fastdfs_tpu.common.fileid import decode_file_id
from fastdfs_tpu.common.protocol import (StorageCmd, pack_chunking,
                                         pack_group_name, unpack_chunking)
from fastdfs_tpu.ops import gear_cdc
from fastdfs_tpu.trace import decode_dump
from harness import (Sidecar, chunk_digests, recipe_keys, start_storage,
                     start_tracker, upload_retry)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
sys.path.insert(0, os.path.join(REPO, "tools"))
import reference  # noqa: E402  — benchmark/reference.py
import reference_negotiated  # noqa: E402
from access_log_stages import aggregate  # noqa: E402
from run import RecipeReader  # noqa: E402  — benchmark/run.py

K, M = 1 << 10, 1 << 20
NARROW = (4 * K, 13, 64 * K)
WIDE = (16 * K, 15, 256 * K)
RESTIC = (512 * K, 20, 8 * M)
HB = "heart_beat_interval = 1\nstat_report_interval = 1"


def _seeded(n: int, seed: int) -> bytes:
    return np.random.default_rng(seed).bytes(n)


def _params(widths, policy=1, segment=M) -> ChunkingParams:
    return ChunkingParams(*widths, policy, 64 * K, segment)


def _widths(widths, segment=M) -> dict:
    return {"cdc_min_size": widths[0], "cdc_avg_bits": widths[1],
            "cdc_max_size": widths[2], "shingle": 5, "num_perms": 64,
            "dedup_chunk_threshold": 64 * K, "dedup_segment_bytes": segment}


def _conf(widths, segment="1M") -> str:
    return ("dedup_cdc_widths = %d:%d:%d\n" % widths
            + f"dedup_segment_bytes = {segment}\n{HB}")


def _recipe(fps):
    return [(fp.length, fp.digest) for fp in fps]


def _fetch(st, fid):
    reader = RecipeReader(st.port)
    try:
        return reader.fetch(fid)
    finally:
        reader.close()


# -- (a) the client's cuts at a node's parameters ---------------------------------

@pytest.mark.parametrize("policy", [gear_cdc.CDC_POLICY_DEFAULT,
                                    gear_cdc.CDC_POLICY_SKIPMIN])
@pytest.mark.parametrize("widths", [NARROW, WIDE], ids=["narrow", "wide"])
def test_client_cuts_each_segment_as_the_reference_does(widths, policy):
    data = _seeded(2 * M + M // 2, 41)
    got = _recipe(fingerprint_buffer(data, _params(widths, policy)))
    assert sum(n for n, _ in got) == len(data)
    if policy == gear_cdc.CDC_POLICY_DEFAULT:
        want = reference.recipe(data, _widths(widths))
    else:       # the reference knows one policy: the program's serial referee
        want = []
        for base in range(0, len(data), M):
            seg, last = data[base:base + M], 0
            for cut in gear_cdc.chunk_stream_skipmin_ref(seg, *widths):
                want.append((cut - last, hashlib.sha1(seg[last:cut]).digest()))
                last = cut
    assert got == want
    # a segment end is a cut: some chunk ends at every multiple of 1 MB
    ends = set(np.cumsum([n for n, _ in got]).tolist())
    assert {M, 2 * M} <= ends
    # and cutting the whole buffer at once (what the client did before it
    # asked its node) is another recipe
    whole = _recipe(fingerprint_buffer(data, _params(widths, policy, 64 * M)))
    assert whole != got


def test_chunking_blob_roundtrip_and_refusals():
    values = dict(min_size=512 * K, avg_bits=20, max_size=8 * M, cdc_policy=1,
                  chunk_threshold=64 * K, segment_bytes=64 * M)
    blob = pack_chunking(values)
    assert unpack_chunking(blob) == values
    assert unpack_chunking(blob + b"\0" * 8) == values      # append-only
    assert ChunkingParams.from_wire(blob) == ChunkingParams(**values)
    with pytest.raises(ValueError):
        unpack_chunking(blob[:40])                            # a slot short
    with pytest.raises(ValueError):
        unpack_chunking(pack_chunking({**values, "max_size": 1024}))


@pytest.mark.parametrize("widths,segment,size", [
    (NARROW, M, 2 * M + M // 2), (RESTIC, 64 * M, 5 * M)],
    ids=["narrow", "restic"])
def test_node_states_its_parameters_and_the_client_cuts_with_them(
        tmp_path, widths, segment, size):
    """cpu mode (no sidecar): the node takes the recipe as it is, so what
    is stored is what the client cut: it has to be the node's own cut."""
    tr = start_tracker(str(tmp_path / "tr"))
    st = start_storage(str(tmp_path / "st"), dedup_mode="cpu",
                       trackers=[f"127.0.0.1:{tr.port}"],
                       extra=_conf(widths, f"{segment // M}M"))
    cli = FdfsClient([f"127.0.0.1:{tr.port}"])
    data = _seeded(size, 42)
    try:
        with StorageClient(st.ip, st.port) as sc:
            assert sc.query_chunking() == _params(widths, segment=segment)
        upload_retry(cli, b"warmup " * 64, ext="bin")
        stats: dict = {}
        fid = cli.upload_buffer_dedup(data, ext="bin", min_dup_ratio=0,
                                      stats=stats)
        assert stats["fallback"] == "" and cli.stats()[
            "dedup_fallback_plain"] == 0
        got, logical = _fetch(st, fid)
        # the same bytes by a plain upload: the node's own cut
        with StorageClient(st.ip, st.port) as sc:
            plain, _ = _fetch(st, sc.upload_buffer(data, ext="bin"))
        assert cli.download_to_buffer(fid) == data
    finally:
        cli.close()
        st.stop()
        tr.stop()
    assert logical == len(data)
    assert got == plain == reference.recipe(data, _widths(widths, segment))
    assert max(n for n, _ in got) > SHIPPED_PARAMS.max_size or widths == NARROW


# -- (b) live cluster, the sidecar on the CPU --------------------------------------

@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    base = tmp_path_factory.mktemp("negotiated")
    sc = Sidecar(str(base / "sc"),
                 ("--platform", "cpu", "--cdc-widths", "%d:%d:%d" % NARROW),
                 state_dir=str(base / "state"))
    tr = start_tracker(str(base / "tr"))
    st = start_storage(str(base / "st"), dedup_mode="sidecar",
                       dedup_sidecar=sc.sock,
                       trackers=[f"127.0.0.1:{tr.port}"],
                       extra=_conf(NARROW) + "\nuse_access_log = 1")
    cli = FdfsClient([f"127.0.0.1:{tr.port}"], timeout=120.0)
    upload_retry(cli, b"warmup " * 64, ext="bin")
    box = {"sidecar": sc, "tracker": tr, "storage": st, "cli": cli,
           "base": str(base)}
    yield box
    cli.close()
    st.stop()
    tr.stop()
    sc.stop()


def _counters(st) -> dict:
    with StorageClient(st.ip, st.port) as sc:
        return sc.stat()["counters"]


def test_negotiated_generation_equals_both_references(cluster):
    cli, st = cluster["cli"], cluster["storage"]
    widths = _widths(NARROW)
    gen0 = _seeded(2 * M + M // 2, 43)
    # one edit that shifts every later offset: the segment ends fall on
    # other bytes of the content than in generation 0
    gen1 = gen0[:300 * K] + _seeded(5000, 44) + gen0[308 * K:]
    fid0 = cli.upload_buffer(gen0, ext="bin")
    before = _counters(st)
    stats: dict = {}
    fid1 = cli.upload_buffer_dedup(gen1, ext="bin", min_dup_ratio=0,
                                   stats=stats)
    after = _counters(st)
    assert stats["fallback"] == ""
    want, mask, sent = reference_negotiated.exchange([gen0], gen1, widths)
    got, logical = _fetch(st, fid1)
    assert logical == len(gen1) and got == want
    assert _fetch(st, fid0)[0] == reference.recipe(gen0, widths)
    assert stats["chunks_total"] == len(want)
    assert stats["chunks_missing"] == sum(mask) and stats["bytes_sent"] == sent
    assert 0 < sum(mask) < len(mask) // 2      # most of it was there
    assert cli.download_to_buffer(fid1) == gen1

    def delta(name):
        return after.get(name, 0) - before.get(name, 0)
    assert delta("ingest.recipe_uploads") == 1
    assert delta("ingest.recipe_fallbacks") == 0
    assert delta("ingest.chunks_shipped") == sum(mask)
    assert delta("ingest.chunks_present") == len(mask) - sum(mask)
    assert delta("ingest.bytes_saved_wire") == len(gen1) - sent
    # the sidecar was asked for the whole file again, and says who asked
    sc_stats = cluster["sidecar"].stats()
    assert sc_stats["reindex_bytes"] == len(gen1)
    assert sc_stats["reindex_requests"] == 3       # three segments
    cluster["gen1"] = (fid1, gen1)


def test_recipe_cut_under_other_parameters_is_refused_by_the_node(cluster):
    """A client that brings its own cut (here: the shipped widths, against
    a node at 4K:13:64K) is rolled back at the commit and ends in a plain
    upload; what is stored is the node's own cut of the bytes."""
    st = cluster["storage"]
    data = _seeded(M + M // 2, 45)
    foreign = _recipe(fingerprint_buffer(data, SHIPPED_PARAMS))
    assert foreign != reference.recipe(data, _widths(NARROW))
    before = _counters(st)
    stats: dict = {}
    with StorageClient(st.ip, st.port, timeout=120.0) as sc:
        fid = sc.upload_buffer_dedup(data, ext="bin", chunks=foreign,
                                     stats=stats)
        assert sc.download_to_buffer(fid) == data
    after = _counters(st)
    assert stats["fallback"] == "commit_status22"
    assert (after["ingest.recipe_fallbacks"]
            - before.get("ingest.recipe_fallbacks", 0)) == 1
    assert after.get("ingest.recipe_uploads", 0) == before.get(
        "ingest.recipe_uploads", 0)
    assert _fetch(st, fid)[0] == reference.recipe(data, _widths(NARROW))
    assert "the client's recipe entry" in st.stderr_text + st.stdout_text


def test_signature_and_stages_of_the_negotiated_generation(cluster):
    """Last of the module: stops the daemons to read what they wrote."""
    fid1, gen1 = cluster["gen1"]
    cluster["cli"].close()
    cluster["storage"].stop()
    cluster["sidecar"].stop()
    near = np.load(os.path.join(cluster["base"], "state", "sidecar_near.npz"),
                   allow_pickle=True)
    sigs = {json.loads(str(ref)): sig
            for ref, sig in zip(near["refs"], near["sigs"])}
    assert np.array_equal(np.asarray(sigs[fid1], np.uint32),
                          reference.file_signature(gen1, _widths(NARROW)))
    agg = aggregate(os.path.join(cluster["base"], "st", "logs", "access.log"))
    assert agg["query_chunking"]["count"] == 1      # asked once, then cached
    recipe, commit = agg["upload_recipe"], agg["upload_chunks"]
    assert recipe["count"] == 2 and commit["count"] == 2
    assert commit["errors"] == 1                    # the foreign recipe
    # the five columns follow the sixteen there were, on every row
    with open(os.path.join(cluster["base"], "st", "logs", "access.log")) as fh:
        rows = [ln.split() for ln in fh if not ln.startswith("{")]
    assert {len(f) for f in rows} == {21}
    names = ("negotiate_us", "present_us", "verify_us", "recipe_us",
             "reindex_us")
    by_cmd = {cmd: [dict(zip(names, map(int, f[16:])), cswrite_us=int(f[10]))
                    for f in rows if f[2] == cmd and f[3] == "0"]
              for cmd in ("11", "132", "133")}
    assert all(r["negotiate_us"] > 0 and r["reindex_us"] == 0
               for r in by_cmd["132"])
    (done,) = by_cmd["133"]
    assert all(done[n] > 0 for n in names[1:]) and done["negotiate_us"] == 0
    assert (done["present_us"] + done["verify_us"] + done["recipe_us"]
            <= done["cswrite_us"])
    assert all(r[n] == 0 for r in by_cmd["11"] for n in names)


# -- (c) a client that cannot learn its node's parameters ---------------------------

class _OldDaemon:
    """A connection to a daemon from before QUERY_CHUNKING: the unknown
    opcode is answered EINVAL; a plain upload works."""

    def __init__(self):
        self.sent = []

    def send_request(self, cmd, body=b"", body_len=None):
        self.sent.append(StorageCmd(cmd))

    def recv_response(self, what):
        if self.sent[-1] == StorageCmd.QUERY_CHUNKING:
            raise StatusError(22, what)
        assert self.sent[-1] == StorageCmd.UPLOAD_FILE
        return pack_group_name("group1") + b"M00/00/00/plain.bin"

    def close(self):
        pass


def test_client_that_cannot_learn_the_parameters_uploads_plain(monkeypatch):
    conn = _OldDaemon()
    stats: dict = {}
    fid = StorageClient("old", 1, conn=conn).upload_buffer_dedup(
        _seeded(200 * K, 46), ext="bin", stats=stats)
    assert fid == "group1/M00/00/00/plain.bin"
    assert stats["fallback"] == "no_chunking_params"
    assert conn.sent == [StorageCmd.QUERY_CHUNKING, StorageCmd.UPLOAD_FILE]

    # and through FdfsClient: counted, no recipe sent, nothing remembered
    from fastdfs_tpu.client.tracker_client import StoreTarget
    cli = FdfsClient("127.0.0.1:1", timeout=0.1, use_pool=False)
    conn2 = _OldDaemon()
    monkeypatch.setattr(cli, "_with_tracker", lambda fn: StoreTarget(
        group="group1", ip="old", port=1, store_path_index=0))
    monkeypatch.setattr(cli, "_storage",
                        lambda tgt: StorageClient("old", 1, conn=conn2))
    stats = {}
    assert cli.upload_buffer_dedup(_seeded(200 * K, 47), ext="bin",
                                   min_dup_ratio=0, stats=stats
                                   ) == "group1/M00/00/00/plain.bin"
    assert stats["fallback"] == "no_chunking_params"
    assert cli.stats()["dedup_fallback_plain"] == 1
    assert StorageCmd.UPLOAD_RECIPE not in conn2.sent
    assert conn2.sent == [StorageCmd.QUERY_CHUNKING, StorageCmd.UPLOAD_FILE]
    assert cli._chunking == {}


# -- (d) a shipped chunk that is not its digest --------------------------------------

def test_shipped_chunk_that_is_not_its_digest_is_refused(tmp_path):
    st = start_storage(str(tmp_path / "st"), dedup_mode="cpu", extra=HB)
    data = _seeded(300 * K, 48)
    honest = _recipe(fingerprint_buffer(data, SHIPPED_PARAMS))
    lie = hashlib.sha1(b"not these bytes").digest()
    lying = [honest[0], (honest[1][0], lie)] + honest[2:]
    try:
        stats: dict = {}
        with StorageClient(st.ip, st.port) as sc:
            before = sc.stat()["counters"].get("ingest.recipe_fallbacks", 0)
            fid = sc.upload_buffer_dedup(data, ext="bin", chunks=lying,
                                         stats=stats)
            assert stats["fallback"] == "commit_status5"
            assert sc.download_to_buffer(fid) == data      # the plain re-send
            after = sc.stat()["counters"]["ingest.recipe_fallbacks"]
        assert after == before + 1
        assert "failed digest check" in st.stderr_text + st.stdout_text
        stored = chunk_digests(str(tmp_path / "st"))
        assert lie.hex() not in stored
        assert honest[1][1].hex() in stored                 # by its own name
        assert _fetch(st, fid)[0] == honest
    finally:
        st.stop()


# -- (e) the commit's one pass over each segment -------------------------------------

def _negotiate(sc, data, chunks):
    """Phase 1 alone: (session, mask) for the recipe `chunks` of `data`."""
    sc.conn.send_request(StorageCmd.UPLOAD_RECIPE, pack_upload_recipe(
        0xFF, "bin", zlib.crc32(data), len(data), chunks))
    return unpack_upload_recipe_resp(sc.conn.recv_response("upload_recipe"),
                                     len(chunks))


def _commit(sc, session, data, chunks, mask):
    """Phase 2 alone: ships what the mask asks for; the reply's body, or
    StatusError (no fall-back to a plain upload, as the client has)."""
    body, off = [], 0
    for (length, _), need in zip(chunks, mask):
        if need:
            body.append(data[off:off + length])
        off += length
    body = b"".join(body)
    sc.conn.send_request(StorageCmd.UPLOAD_CHUNKS,
                         pack_upload_chunks_prefix(session, len(body)) + body)
    return sc.conn.recv_response("upload_chunks")


def _tmp_files(base) -> list[str]:
    return os.listdir(os.path.join(str(base), "tmp"))


def _gone(base, timeout=10.0) -> bool:
    """Every chunk of the store unlinked: no reference is left on any."""
    deadline = time.time() + timeout
    while chunk_digests(str(base)) and time.time() < deadline:
        time.sleep(0.2)
    return not chunk_digests(str(base))


@pytest.mark.parametrize("mode", ["sidecar", "cpu"])
def test_commit_assembles_each_segment_once(tmp_path, mode):
    base = tmp_path / "st"
    sidecar = None
    if mode == "sidecar":
        sidecar = Sidecar(str(tmp_path / "sc"), (
            "--platform", "cpu", "--cdc-widths", "%d:%d:%d" % NARROW),
            state_dir=str(tmp_path / "state"))
    st = start_storage(str(base), dedup_mode=mode,
                       dedup_sidecar=sidecar.sock if sidecar else "",
                       extra=_conf(NARROW) + "\nslow_request_threshold_ms = 1")
    widths = _widths(NARROW)
    gen0 = _seeded(2 * M + M // 2, 51)
    gen1 = gen0[:300 * K] + _seeded(5000, 52) + gen0[308 * K:]
    try:
        with StorageClient(st.ip, st.port, timeout=120.0) as sc:
            sc.upload_buffer(gen0, ext="bin")
            before = sc.stat()["counters"]
            stats: dict = {}
            fid = sc.upload_buffer_dedup(gen1, ext="bin", stats=stats)
            after = sc.stat()["counters"]
            dump = decode_dump(sc.trace_dump())
            spans = {s.name: s for s in dump}
            assert stats["fallback"] == ""
            assert sc.download_to_buffer(fid) == gen1
        assert _tmp_files(base) == []       # nothing materialised, nothing left
        want, mask, _ = reference_negotiated.exchange([gen0], gen1, widths)
        assert _fetch(st, fid) == (want, len(gen1))
        assert decode_file_id(fid)[1].crc32 == zlib.crc32(gen1)

        def delta(name):
            return after.get(name, 0) - before.get(name, 0)
        assert delta("ingest.recipe_uploads") == 1
        assert delta("ingest.recipe_fallbacks") == 0
        # the present chunks under slab_chunk_threshold came many to a preadv
        in_slab = sum(1 for (n, _), need in zip(want, mask)
                      if not need and n < 64 * K)
        assert in_slab > 100
        assert delta("ingest.commit_read_chunks") == in_slab
        assert 0 < delta("ingest.commit_read_batches") < in_slab // 4
        # and the commit's spans say the same of themselves: one present
        # span a segment, each with the reads it made
        present = {s.span_id for s in dump
                   if s.name == "storage.commit.present"}
        reads = [s for s in dump if s.name.startswith("ingest.commit_reads ")]
        assert len(present) == 3 and len(reads) == 3
        assert {s.parent_id for s in reads} == present
        assert [sum(int(s.name.split()[1].split("/")[i]) for s in reads)
                for i in (0, 1)] == [in_slab,
                                     delta("ingest.commit_read_batches")]
        assert ("storage.reindex" in spans) == (mode == "sidecar")
    finally:
        st.stop()
        if sidecar:
            sidecar.stop()
    if sidecar:         # stopped: it has written what it keeps
        near = np.load(str(tmp_path / "state" / "sidecar_near.npz"),
                       allow_pickle=True)
        sigs = {json.loads(str(ref)): sig
                for ref, sig in zip(near["refs"], near["sigs"])}
        assert np.array_equal(np.asarray(sigs[fid], np.uint32),
                              reference.file_signature(gen1, widths))


@pytest.mark.parametrize("case,status,says", [
    ("entry_across_a_segment_end", 22, "the client's recipe entry"),
    ("entry_longer_than_a_segment", 22, "the client's recipe entry"),
    ("present_chunk_of_another_length", 5, "bytes in the store"),
    ("chunk_gone_before_commit", 5, "vanished before commit")])
def test_commit_that_cannot_stand_gives_every_reference_back(tmp_path, case,
                                                             status, says):
    base = tmp_path / "st"
    st = start_storage(str(base), dedup_mode="cpu", extra=_conf(NARROW))
    gen0 = _seeded(2 * M + M // 2, 53)
    node_cut = _recipe(fingerprint_buffer(gen0, _params(NARROW)))
    if case == "entry_across_a_segment_end":
        # the whole buffer cut at once: what a client cut before it asked
        chunks = _recipe(fingerprint_buffer(gen0, _params(NARROW, 1, 64 * M)))
        ends = set(np.cumsum([n for n, _ in chunks]).tolist())
        assert M not in ends
    elif case == "entry_longer_than_a_segment":
        # the second segment as one entry of 1 MB + 1: no place in the buffer
        ends = np.cumsum([n for n, _ in node_cut]).tolist()
        chunks = node_cut[:ends.index(M) + 1] + [
            (M + 1, hashlib.sha1(gen0[M:2 * M + 1]).digest()),
            (M // 2 - 1, hashlib.sha1(gen0[2 * M + 1:]).digest())]
    elif case == "present_chunk_of_another_length":
        # one byte moved from an entry to its neighbour: both digests are
        # in the store, under other lengths
        (n0, d0), (n1, d1) = node_cut[5], node_cut[6]
        chunks = node_cut[:5] + [(n0 + 1, d0), (n1 - 1, d1)] + node_cut[7:]
    else:
        chunks = node_cut
    assert sum(n for n, _ in chunks) == len(gen0)
    try:
        with StorageClient(st.ip, st.port, timeout=60.0) as sc:
            fid0 = sc.upload_buffer(gen0, ext="bin")
            recipes = recipe_keys(str(base))
            before = sc.stat()["counters"]
            session, mask = _negotiate(sc, gen0, chunks)
            if case == "chunk_gone_before_commit":
                assert not any(mask)
                with StorageClient(st.ip, st.port) as other:
                    other.delete_file(fid0)     # the pins keep only the bytes
                recipes = set()
            with pytest.raises(StatusError) as refused:
                _commit(sc, session, gen0, chunks, mask)
            assert refused.value.status == status
            after = sc.stat()["counters"]
            assert (after["ingest.recipe_fallbacks"]
                    - before.get("ingest.recipe_fallbacks", 0)) == 1
            assert after.get("ingest.recipe_uploads", 0) == before.get(
                "ingest.recipe_uploads", 0)
            assert recipe_keys(str(base)) == recipes    # no recipe on disk
            assert _tmp_files(base) == []
            if case != "chunk_gone_before_commit":
                assert sc.download_to_buffer(fid0) == gen0
                sc.delete_file(fid0)
        # the one file that referenced them is deleted: a chunk that is
        # still there holds a reference the failed commit did not give back
        assert _gone(base), sorted(chunk_digests(str(base)))[:3]
    finally:
        st.stop()
    assert says in st.stderr_text + st.stdout_text


def test_digest_repeated_in_one_recipe_reads_right_each_time(tmp_path):
    base = tmp_path / "st"
    st = start_storage(str(base), dedup_mode="cpu", extra=_conf(NARROW))
    block = _seeded(300 * K, 54)
    # the block four times, over two segments: cold, every occurrence is
    # shipped (the first put, the later ones found); warm, all are read
    cold = _seeded(100 * K, 55) + block * 4 + _seeded(50 * K, 56)
    warm = block * 3 + _seeded(20 * K, 57) + block * 2
    try:
        with StorageClient(st.ip, st.port, timeout=60.0) as sc:
            for data, all_new in ((cold, True), (warm, False)):
                want = reference.recipe(data, _widths(NARROW))
                digests = [d for _, d in want]
                assert len(set(digests)) < len(digests) - 20
                stats: dict = {}
                fid = sc.upload_buffer_dedup(data, ext="bin", stats=stats)
                assert stats["fallback"] == ""
                assert (stats["chunks_missing"] == len(want)) == all_new
                assert _fetch(st, fid) == (want, len(data))
                assert decode_file_id(fid)[1].crc32 == zlib.crc32(data)
                assert sc.download_to_buffer(fid) == data
    finally:
        st.stop()
