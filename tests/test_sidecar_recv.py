"""How a fingerprint body crosses into the sidecar (``sidecar.py:_serve_conn``
and ``_recv_into``): one buffer a connection, filled in place by one
receive call a body, handed to the fingerprint handlers as a view.

A ``--platform cpu`` sidecar is started as ``tests/test_sidecar_spans.py``
starts one and spoken to over its real ``AF_UNIX`` socket; the cut check
and what an accepted socket looks like are read from a ``DedupSidecar`` in
this process.
"""

from __future__ import annotations

import hashlib
import os
import socket
import struct
import threading

import numpy as np
import pytest

from fastdfs_tpu.common.protocol import HEADER_SIZE, StorageCmd, unpack_header
from fastdfs_tpu.sidecar import DedupSidecar, _cuts_cover, read_stats, rpc
from harness import Sidecar

FP_CUTS = StorageCmd.DEDUP_FINGERPRINT_CUTS
CHUNK = 2048
# A Unix stream socket hands over about 200 KB a ``recv``: LARGE is many of
# those, SMALL fits one.
LARGE, SMALL = 1200 * CHUNK, 3 * CHUNK


def cuts_body(session: int, ends: list[int], data: bytes,
              n_cuts: int | None = None) -> bytes:
    return (struct.pack(">qqq", session, 0,
                        len(ends) if n_cuts is None else n_cuts)
            + struct.pack(f">{len(ends)}q", *ends) + data)


def request(seed: int, size: int) -> tuple[bytes, bytes]:
    """A DEDUP_FINGERPRINT_CUTS body of ``size`` payload bytes, and the
    reply hashlib says it must get."""
    data = np.random.default_rng(seed).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    ends = list(range(CHUNK, size + 1, CHUNK))
    want = struct.pack(">q", len(ends)) + b"".join(
        struct.pack(">qq", e - CHUNK, CHUNK)
        + hashlib.sha1(data[e - CHUNK:e]).digest() for e in ends)
    return cuts_body(seed, ends, data), want


def connect(path: str) -> socket.socket:
    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    s.settimeout(600.0)
    s.connect(path)
    return s


def read_reply(s: socket.socket) -> tuple[int, bytes] | None:
    """(status, body), or None when the sidecar closed without a reply."""
    hdr = DedupSidecar._recv_exact(s, HEADER_SIZE)
    if hdr is None:
        return None
    h = unpack_header(hdr)
    return h.status, DedupSidecar._recv_exact(s, h.pkg_len)


def send_in_pieces(s: socket.socket, msg: bytes, sizes) -> None:
    at = 0
    while at < len(msg):
        n = next(sizes)
        s.sendall(msg[at:at + n])
        at += n


@pytest.fixture(scope="module")
def sidecar(tmp_path_factory):
    sc = Sidecar(os.path.join(str(tmp_path_factory.mktemp("recv")), "sc"),
                 ("--platform", "cpu"))
    try:
        yield sc
    finally:
        sc.stop()


def pieces(kind: str):
    if kind == "1K":
        return iter(lambda: 1024, None)
    if kind == "64K":
        return iter(lambda: 65536, None)
    rng = np.random.default_rng(27)     # 1-64 KB, header split too
    return iter(lambda: int(rng.integers(1, 65537)), None)


@pytest.mark.parametrize("how", ["one_sendall", "1K", "64K", "1B-64K"])
def test_reply_does_not_depend_on_how_the_body_arrives(sidecar, how):
    body, want = request(101, LARGE)
    msg = struct.pack(">qBB", len(body), FP_CUTS, 0) + body
    with connect(sidecar.sock) as s:
        if how == "one_sendall":
            s.sendall(msg)
        else:
            send_in_pieces(s, msg, pieces(how))
        assert read_reply(s) == (0, want)


@pytest.mark.parametrize("sizes", [(LARGE, SMALL, LARGE),
                                   (SMALL, LARGE, SMALL),
                                   (LARGE, LARGE - CHUNK, 0, CHUNK)],
                         ids=["large-small-large", "small-large-small",
                              "shrinking-to-empty"])
def test_requests_on_one_connection_share_no_bytes(sidecar, sizes):
    with connect(sidecar.sock) as s:
        for i, size in enumerate(sizes):
            body, want = request(200 + i, size)
            s.sendall(struct.pack(">qBB", len(body), FP_CUTS, 0) + body)
            assert read_reply(s) == (0, want), (i, size)


@pytest.mark.parametrize("sent", ["half_a_header", "header_only",
                                  "half_a_body", "all_but_one_byte"])
def test_peer_closing_mid_request_gets_no_reply_and_the_next_is_served(
        sidecar, sent):
    body, want = request(301, LARGE)
    msg = struct.pack(">qBB", len(body), FP_CUTS, 0) + body
    upto = {"half_a_header": HEADER_SIZE // 2, "header_only": HEADER_SIZE,
            "half_a_body": HEADER_SIZE + len(body) // 2,
            "all_but_one_byte": len(msg) - 1}[sent]
    before = read_stats(sidecar.sock)
    with connect(sidecar.sock) as s:
        s.sendall(msg[:upto])
        s.shutdown(socket.SHUT_WR)
        assert read_reply(s) is None
    with connect(sidecar.sock) as s:
        s.sendall(msg)
        assert read_reply(s) == (0, want)
    after = read_stats(sidecar.sock)
    # only the complete body was counted and fingerprinted
    assert after["recv_bytes"] - before["recv_bytes"] == len(body)
    assert after["fingerprint_bytes"] - before["fingerprint_bytes"] == LARGE


@pytest.mark.parametrize("size", [0, SMALL, LARGE, 4 * LARGE],
                         ids=["empty", "small", "large", "10MB"])
def test_a_body_sent_at_once_is_received_in_a_few_calls(sidecar, size):
    body, want = request(400 + size % 97, size)
    before = read_stats(sidecar.sock)
    assert rpc(sidecar.sock, FP_CUTS, body, 600.0) == (0, want)
    # other opcodes (the stats calls themselves) are not counted
    assert rpc(sidecar.sock, StorageCmd.ACTIVE_TEST) == (0, b"")
    after = read_stats(sidecar.sock)
    assert after["recv_bytes"] - before["recv_bytes"] == len(body)
    calls = after["recv_calls"] - before["recv_calls"]
    assert 1 <= calls <= 3, calls


# -- the cut check -------------------------------------------------------------

def old_cover_check(cuts: list[int], n: int) -> bool:
    """The scalar predicate ``_fingerprint`` had before the offsets were
    read with one ``np.frombuffer``: the reference."""
    if n:
        return not (not cuts or cuts[-1] != n
                    or any(c <= p for p, c in zip([0] + cuts, cuts)))
    return not cuts


DATA = bytes(range(256)) * 32       # 8 KB
CUT_CASES = {
    # name: (ends, payload, n_cuts field or None for len(ends), accepted)
    "covering": ([2048, 4096, 8192], DATA, None, True),
    "one_chunk": ([8192], DATA, None, True),
    "nothing_at_all": ([], b"", None, True),
    "empty_cuts_with_data": ([], DATA, None, False),
    "cuts_with_no_data": ([2048], b"", None, False),
    "zero_cut_with_no_data": ([0], b"", None, False),
    "last_cut_short": ([2048, 8191], DATA, None, False),
    "last_cut_beyond": ([2048, 8193], DATA, None, False),
    "repeated_cut": ([2048, 2048, 8192], DATA, None, False),
    "decreasing_cut": ([4096, 2048, 8192], DATA, None, False),
    "zero_first_cut": ([0, 2048, 8192], DATA, None, False),
    "negative_first_cut": ([-1, 2048, 8192], DATA, None, False),
    "wrapping_difference": ([-(1 << 63), (1 << 63) - 1, 8192], DATA, None,
                            False),
    "n_cuts_negative": ([2048, 8192], DATA, -1, False),
    "n_cuts_beyond_the_body": ([8192], DATA, 1 + (8 + len(DATA)) // 8 + 1,
                               False),
    "n_cuts_huge": ([8192], DATA, 1 << 60, False),
}


@pytest.fixture(scope="module")
def in_process(tmp_path_factory):
    return DedupSidecar(os.path.join(
        str(tmp_path_factory.mktemp("cuts")), "x.sock"))


@pytest.mark.parametrize("case", list(CUT_CASES))
@pytest.mark.parametrize("buffer", [bytes, memoryview])
def test_malformed_cuts_are_refused_as_the_scalar_check_refused_them(
        in_process, case, buffer):
    ends, data, n_cuts, accepted = CUT_CASES[case]
    if n_cuts is None:      # the old predicate saw only well-framed bodies
        assert old_cover_check(ends, len(data)) == accepted
    body = buffer(cuts_body(500, ends, data, n_cuts))
    status, reply = in_process._fingerprint(body, with_cuts=True)
    assert status == (0 if accepted else 22)
    if accepted:
        assert struct.unpack_from(">q", reply)[0] == len(ends)
        last = 0
        for i, e in enumerate(ends):
            assert reply[8 + 36 * i:8 + 36 * (i + 1)] == (
                struct.pack(">qq", last, e - last)
                + hashlib.sha1(data[last:e]).digest())
            last = e
    else:
        assert reply == b""


@pytest.mark.parametrize("seed", range(4))
def test_vectorised_cover_check_is_the_scalar_one(seed):
    rng = np.random.default_rng(seed)
    for _ in range(2000):
        n = int(rng.integers(0, 6))
        cuts = sorted(rng.integers(-1, 8, int(rng.integers(0, 5))).tolist())
        if rng.integers(0, 4) == 0:
            rng.shuffle(cuts)
        ends = np.array(cuts, dtype=">i8")
        assert _cuts_cover(ends, n) == old_cover_check(cuts, n), (cuts, n)


# -- the accepted socket, the kept buffer, and stopping ------------------------

def serving(sc: DedupSidecar) -> threading.Thread:
    ready = threading.Event()
    server = threading.Thread(target=sc.serve_forever, args=(ready,),
                              daemon=True)
    server.start()
    assert ready.wait(10.0)
    return server


@pytest.mark.parametrize("handler", ["the_sidecar's", "one_that_keeps_a_view"])
def test_no_view_of_the_buffer_outlives_the_reply(tmp_path, handler):
    """The buffer is the connection's and the next request overwrites it.
    A ``bytearray`` refuses to be resized while any view of it is alive:
    after a reply nothing of the real handlers (slices, the cut array, the
    engine's ``frombuffer``) holds one; the control shows the probe bites."""
    buffers, stash = [], []

    class Watched(DedupSidecar):
        def _handle(self, cmd, body, acc):
            if cmd == FP_CUTS:
                buffers.append(body.obj)
                if handler == "one_that_keeps_a_view":
                    stash.append(body[24:])
            return super()._handle(cmd, body, acc)

    sc = Watched(os.path.join(str(tmp_path), "x.sock"))
    server = serving(sc)
    try:
        with connect(sc.socket_path) as s:
            for seed in (601, 602):     # the second reuses the buffer
                body, want = request(seed, SMALL)
                s.sendall(struct.pack(">qBB", len(body), FP_CUTS, 0) + body)
                assert read_reply(s) == (0, want)
            assert buffers[0] is buffers[1]
            if handler == "the_sidecar's":
                buffers[0].extend(b"\0")
            else:
                with pytest.raises(BufferError):
                    buffers[0].extend(b"\0")
    finally:
        sc.stop()
        server.join(10.0)
    assert not server.is_alive()


def test_accepted_socket_blocks_and_stop_does_not_wait_for_an_idle_one(
        tmp_path):
    seen = []

    class Watched(DedupSidecar):
        def _serve_conn(self, conn):
            seen.append((conn.getblocking(), conn.gettimeout()))
            super()._serve_conn(conn)

    sc = Watched(os.path.join(str(tmp_path), "x.sock"))
    server = serving(sc)
    assert sc._listener.gettimeout() == 0.5
    with connect(sc.socket_path) as idle:
        # served, then left open: its thread sits in the next header read
        idle.sendall(struct.pack(">qBB", 0, StorageCmd.ACTIVE_TEST, 0))
        assert read_reply(idle) == (0, b"")
        # MSG_WAITALL waits inside the kernel, past any Python-level
        # timeout: the accepted socket must have none.
        assert seen == [(True, None)]
        sc.stop()
        server.join(10.0)
        assert not server.is_alive()
    assert not os.path.exists(sc.socket_path)
