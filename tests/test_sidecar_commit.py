"""The sidecar's fingerprint reply and its commit, in-process (no daemon).

The reply is one array of (offset, length, SHA1) records, which
``SidecarDedup::FingerprintChunks`` (``native/storage/dedup.cc``) parses:
it must be byte for byte what the per-chunk loop it replaced built.  A
commit puts its session's digests into the exact index as one batch: the
attributions must be those of inserting them one at a time, in order,
first writer wins.
"""

from __future__ import annotations

import hashlib
import struct

import numpy as np
import pytest

from fastdfs_tpu.sidecar import DedupSidecar


def _old_reply(spans, digests, base_offset: int) -> bytes:
    """The reference: the reply as the per-chunk loop built it."""
    raw = np.asarray(digests, dtype=">u4").tobytes()
    out = [struct.pack(">q", len(spans))]
    for i, (off, ln) in enumerate(spans):
        out.append(struct.pack(">q", base_offset + off))
        out.append(struct.pack(">q", ln))
        out.append(raw[i * 20:(i + 1) * 20])
    return b"".join(out)


def _ends(lengths) -> list[int]:
    return [int(e) for e in np.cumsum(lengths)]


def _body(session: int, base_offset: int, data: bytes,
          ends: list[int] | None) -> bytes:
    if ends is None:
        return struct.pack(">qq", session, base_offset) + data
    return (struct.pack(">qqq", session, base_offset, len(ends))
            + struct.pack(f">{len(ends)}q", *ends) + data)


@pytest.fixture(scope="module")
def sidecar(tmp_path_factory):
    return DedupSidecar(str(tmp_path_factory.mktemp("commit") / "s.sock"))


@pytest.mark.parametrize("with_cuts", [False, True])
def test_fingerprint_reply_is_the_old_loops_bytes(sidecar, with_cuts):
    rng = np.random.default_rng(42 + with_cuts)
    data = rng.integers(0, 256, 160_000, dtype=np.uint8).tobytes()
    ends = (sorted(set(rng.integers(1, len(data), 60).tolist()))
            + [len(data)] if with_cuts else None)
    base = (3 << 33) + 12_345
    status, reply = sidecar._fingerprint(_body(7000 + with_cuts, base, data,
                                               ends), with_cuts=with_cuts)
    assert status == 0
    spans, digests, _ = sidecar.engine.fingerprint(data, cuts=ends)
    assert reply == _old_reply(spans, digests, base)
    # ... and those bytes say what the chunks are, read back as the
    # daemon reads them
    n = struct.unpack_from(">q", reply)[0]
    assert n == len(spans) >= (len(ends) if ends else 2)
    covered = 0
    for i in range(n):
        off, ln = struct.unpack_from(">qq", reply, 8 + 36 * i)
        assert off - base == covered and ln > 0
        assert reply[8 + 36 * i + 16:8 + 36 * (i + 1)] == hashlib.sha1(
            data[covered:covered + ln]).digest()
        covered += ln
    assert covered == len(data)
    sidecar._commit(f"abort {7000 + with_cuts}".encode())


def test_interleaved_sessions_commit_first_writer_wins(tmp_path):
    sc = DedupSidecar(str(tmp_path / "s.sock"))
    rng = np.random.default_rng(5)
    chunk = [rng.integers(0, 256, int(k), dtype=np.uint8).tobytes()
             for k in rng.integers(256, 4096, 40)]
    # A: chunks 0..29, chunk 3 again; B: chunks 20..39 (ten of them A's
    # too), its own chunk 35 twice and A's chunk 5
    files = {"A": [*range(30), 3], "B": [*range(20, 40), 35, 35, 5]}
    sessions = {"A": 101, "B": 202}
    fids = {"A": "group1/M00/00/00/a.bin", "B": "group1/M00/00/00/b.bin"}
    segments = []       # (file, base offset, chunks) in the order sent
    for half in (0, 1):
        for f, order in files.items():
            cut = len(order) // 2
            part = order[:cut] if half == 0 else order[cut:]
            base = 0 if half == 0 else sum(len(chunk[c]) for c in order[:cut])
            segments.append((f, base, part))
    for f, base, part in segments:
        data = b"".join(chunk[c] for c in part)
        ends = _ends([len(chunk[c]) for c in part])
        assert sc._fingerprint(_body(sessions[f], base, data, ends),
                               with_cuts=True)[0] == 0
    # the referee: every chunk of A, then of B, inserted one at a time
    want: dict[bytes, list] = {}
    for f in ("A", "B"):
        off = 0
        for c in files[f]:
            want.setdefault(hashlib.sha1(chunk[c]).digest(), [fids[f], off])
            off += len(chunk[c])
        assert sc._commit(f"commitchunks {sessions[f]} {fids[f]}".encode()) \
            == (0, b"")
    assert sc._sessions == {}
    assert dict(sc.engine.exact.items()) == want
    assert len(sc.engine.exact) == len(want) == 40
    assert sc.engine.exact.stats() == {"exact_insert_batches": 2,
                                       "exact_inserted": 40,
                                       "exact_merges": 0}
