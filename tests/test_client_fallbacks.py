"""FdfsClient.stats(): the client-side fallback counters, plus the
connection pool's multiplexing-cap and hygiene behavior (ISSUE 18).

Every resilience path in the client is transparent — the call still
succeeds — so these counters are the ONLY place their frequency shows.
Each test drives exactly one fallback with monkeypatched internals (no
daemons): dedup upload -> plain, placement shortcut -> tracker hop,
parallel ranged download -> single stream.  The pool tests drive
acquire/release/sweep with fake connections and injected clocks — no
sockets, no sleeps beyond the bounded cap wait.
"""

import dataclasses
import threading
import time

import pytest

from fastdfs_tpu.client.client import FdfsClient
from fastdfs_tpu.client.conn import ConnectionPool, StatusError
from fastdfs_tpu.client.fingerprint import SHIPPED_PARAMS
from fastdfs_tpu.client.tracker_client import StoreTarget


def _client(**kw) -> FdfsClient:
    # Nothing here may touch the network; use_pool off keeps teardown
    # trivial and any accidental connect fails fast.
    return FdfsClient("127.0.0.1:1", timeout=0.1, use_pool=False, **kw)


def test_stats_starts_zero_and_copies():
    c = _client()
    s = c.stats()
    assert s == {"dedup_fallback_plain": 0,
                 "placement_fallback_tracker": 0,
                 "ranged_fallback_single": 0,
                 "dead_peer_skips": 0,
                 "admission_retry_waits": 0,
                 "hot_route_reads": 0,
                 "hot_fallback_reads": 0}
    s["dedup_fallback_plain"] = 99  # a snapshot, not the live dict
    assert c.stats()["dedup_fallback_plain"] == 0


def test_dedup_small_payload_counts_plain_fallback(monkeypatch):
    c = _client(dedup_uploads=True, dedup_min_bytes=1024)
    monkeypatch.setattr(
        c, "_upload_buffer_plain",
        lambda data, ext="", group=None, appender=False, key=None: "g/p")
    stats: dict = {}
    assert c.upload_buffer_dedup(b"tiny", stats=stats) == "g/p"
    assert stats["fallback"] == "small"
    assert c.stats()["dedup_fallback_plain"] == 1


class _NodeThatSaysHowItCuts:
    """A storage connection that answers QUERY_CHUNKING and nothing else."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def query_chunking(self):
        return dataclasses.replace(SHIPPED_PARAMS, chunk_threshold=1024)


def _route_to_fake_node(monkeypatch, c, storage=_NodeThatSaysHowItCuts):
    tgt = StoreTarget(group="g1", ip="127.0.0.1", port=2,
                      store_path_index=0)
    monkeypatch.setattr(c, "_with_tracker", lambda fn: tgt)
    monkeypatch.setattr(c, "_storage", lambda tgt: storage())


def test_dedup_low_estimate_counts_plain_fallback(monkeypatch):
    c = _client(dedup_uploads=True, dedup_min_bytes=8, dedup_min_ratio=0.5)
    monkeypatch.setattr(
        c, "_upload_buffer_plain",
        lambda data, ext="", group=None, appender=False, key=None: "g/p")
    _route_to_fake_node(monkeypatch, c)
    # A cold digest cache means the estimated dup ratio is 0 < 0.5.
    stats: dict = {}
    assert c.upload_buffer_dedup(b"x" * 4096, stats=stats) == "g/p"
    assert stats["fallback"] == "low_estimate"
    assert c.stats()["dedup_fallback_plain"] == 1


def test_dedup_storage_level_fallback_counts(monkeypatch):
    # The StorageClient session can itself bail to plain (daemon lacks
    # the opcodes / chunk store); it reports through the stats dict and
    # must land in the SAME counter.
    c = _client(dedup_uploads=True, dedup_min_bytes=8, dedup_min_ratio=0)

    class FakeStorage(_NodeThatSaysHowItCuts):
        def upload_buffer_dedup(self, data, ext="", store_path_index=0,
                                chunks=None, stats=None):
            stats.update(fallback="status95", bytes_sent=len(data))
            return "g1/plain"

    _route_to_fake_node(monkeypatch, c, FakeStorage)
    stats: dict = {}
    assert c.upload_buffer_dedup(b"x" * 4096, stats=stats) == "g1/plain"
    assert c.stats()["dedup_fallback_plain"] == 1
    # a node that refused an upload cut with its parameters is asked again
    assert c._chunking == {}


def test_dedup_negotiated_success_counts_nothing(monkeypatch):
    c = _client(dedup_uploads=True, dedup_min_bytes=8, dedup_min_ratio=0)

    class FakeStorage(_NodeThatSaysHowItCuts):
        def upload_buffer_dedup(self, data, ext="", store_path_index=0,
                                chunks=None, stats=None):
            stats.update(fallback="", bytes_sent=0)
            return "g1/dedup"

    _route_to_fake_node(monkeypatch, c, FakeStorage)
    assert c.upload_buffer_dedup(b"x" * 4096) == "g1/dedup"
    assert c.stats()["dedup_fallback_plain"] == 0
    assert list(c._chunking) == [("127.0.0.1", 2)]    # asked once, kept
    # under the node's own threshold there is no recipe to negotiate over
    stats: dict = {}
    monkeypatch.setattr(
        c, "_upload_buffer_plain",
        lambda data, ext="", group=None, appender=False, key=None: "g/p")
    assert c.upload_buffer_dedup(b"x" * 512, stats=stats) == "g/p"
    assert stats["fallback"] == "small"


def test_placement_route_failure_counts_tracker_fallback(monkeypatch):
    c = _client(use_placement=True)
    route = StoreTarget(group="g1", ip="127.0.0.1", port=2,
                        store_path_index=0xFF)
    monkeypatch.setattr(c, "_placement_route", lambda key: route)
    tracker_tgt = StoreTarget(group="g1", ip="127.0.0.1", port=3,
                              store_path_index=0)
    monkeypatch.setattr(c, "_with_tracker", lambda fn: tracker_tgt)

    class Storage:
        def __init__(self, port):
            self.port = port

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def upload_buffer(self, data, ext="", store_path_index=0,
                          appender=False):
            if self.port == 2:  # the placement-routed member is gone
                raise StatusError("upload_file", 16)
            return "g1/via-tracker"

    monkeypatch.setattr(c, "_storage", lambda tgt: Storage(tgt.port))
    assert c._upload_buffer_plain(b"data", key="k") == "g1/via-tracker"
    assert c.stats()["placement_fallback_tracker"] == 1
    assert c._placement is None  # the stale epoch cache was dropped


def test_ranged_failure_counts_single_fallback(monkeypatch):
    c = _client(parallel_downloads=4)

    def boom(fn):
        raise ConnectionError("no tracker")

    monkeypatch.setattr(c, "_with_tracker", boom)
    monkeypatch.setattr(c, "_download_single",
                        lambda file_id, offset=0, length=0: b"whole")
    assert c.download_ranged("g1/x", parallel=4) == b"whole"
    assert c.stats()["ranged_fallback_single"] == 1


def test_ranged_single_range_is_not_a_fallback(monkeypatch):
    # Degenerate splits (parallel <= 1) take the single stream BY
    # DESIGN, not as a failure — they must not pollute the counter.
    c = _client()
    monkeypatch.setattr(c, "_download_single",
                        lambda file_id, offset=0, length=0: b"whole")
    assert c.download_ranged("g1/x", parallel=1) == b"whole"
    assert c.stats()["ranged_fallback_single"] == 0


# ---------------------------------------------------------------------------
# admission sheds (EBUSY + retry-after): the client-side QoS contract
# — an admission refusal is "alive but shedding", NEVER a dead peer
# ---------------------------------------------------------------------------

class _SheddingTracker:
    """Stands in for the TrackerClient context: holds a conn identity so
    _with_tracker can name the endpoint it would (wrongly) condemn."""

    def __init__(self, host="127.0.0.1", port=1):
        self.conn = FakeConn(host, port)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_admission_shed_never_marks_tracker_dead(monkeypatch):
    # The satellite-1 contract: EBUSY + hint must not trip
    # dead_peer_cooldown_s — a shed proves the peer is ALIVE, and a
    # dead-mark would steer a cooldown's worth of traffic toward its
    # equally-loaded (or worse) siblings.  Transport failures still
    # mark dead; that path is pinned further down.
    c = FdfsClient(["127.0.0.1:1", "127.0.0.1:2"], timeout=0.1,
                   use_pool=True)
    sleeps: list[float] = []
    monkeypatch.setattr("fastdfs_tpu.client.client.time",
                        type("T", (), {"sleep": staticmethod(sleeps.append)}))

    def fake_tracker():
        return _SheddingTracker()
    monkeypatch.setattr(c, "_tracker", fake_tracker)

    def shed(t):
        raise StatusError(16, "query_store", retry_after_ms=40)
    with pytest.raises(StatusError) as ei:
        c._with_tracker(shed)
    assert ei.value.status == 16 and ei.value.retry_after_ms == 40
    # No endpoint was condemned, no idle socket purged.
    assert c.pool.dead_mark_count() == 0
    # Every failover attempt honored the hint with bounded jitter:
    # hint <= sleep <= hint * 1.25 (the stampede-breaking spread).
    assert sleeps, "shed retries never slept the retry-after hint"
    assert all(0.040 <= s <= 0.050001 for s in sleeps), sleeps
    assert c.stats()["admission_retry_waits"] == len(sleeps)


def test_ebusy_without_hint_fails_over_without_sleeping(monkeypatch):
    # Hint-less EBUSY predates admission (max_connections refusals,
    # drain, non-leader): failover must stay eager — sleeping would
    # slow the classic path — and still never mark dead.
    c = FdfsClient(["127.0.0.1:1", "127.0.0.1:2"], timeout=0.1,
                   use_pool=True)
    sleeps: list[float] = []
    monkeypatch.setattr("fastdfs_tpu.client.client.time",
                        type("T", (), {"sleep": staticmethod(sleeps.append)}))
    monkeypatch.setattr(c, "_tracker", lambda: _SheddingTracker())

    def busy(t):
        raise StatusError(16, "query_store")  # no retry_after body
    with pytest.raises(StatusError):
        c._with_tracker(busy)
    assert not sleeps
    assert c.pool.dead_mark_count() == 0
    assert c.stats()["admission_retry_waits"] == 0


def test_transport_failure_still_marks_dead(monkeypatch):
    # The counter-case guarding the contract above: an OSError mid-op
    # IS a transport failure and must keep tripping the cooldown.
    c = FdfsClient(["127.0.0.1:1", "127.0.0.1:2"], timeout=0.1,
                   use_pool=True)
    monkeypatch.setattr(c, "_tracker", lambda: _SheddingTracker())

    def die(t):
        raise ConnectionResetError("peer vanished")
    with pytest.raises((OSError, ConnectionError)):
        c._with_tracker(die)
    assert c.pool.dead_mark_count() >= 1


def test_shed_retry_reruns_whole_operation_then_propagates(monkeypatch):
    # _shed_retry re-runs the FULL two-hop closure (a shed answers at
    # request-header stage, so nothing partial ever happened) up to
    # admission_retries times, sleeping the jittered hint between
    # attempts, then lets the EBUSY reach the caller.
    c = _client(admission_retries=2)
    waited: list[int] = []
    monkeypatch.setattr(c, "_admission_wait",
                        lambda e: waited.append(e.retry_after_ms))
    calls = {"n": 0}

    def always_shed():
        calls["n"] += 1
        raise StatusError(16, "upload", retry_after_ms=25)
    with pytest.raises(StatusError):
        c._shed_retry(always_shed)
    assert calls["n"] == 3          # 2 retries + the final propagation run
    assert waited == [25, 25]

    # Success on a retry returns the value and stops consuming budget.
    calls["n"] = 0

    def shed_once():
        calls["n"] += 1
        if calls["n"] == 1:
            raise StatusError(16, "upload", retry_after_ms=25)
        return "g1/ok"
    assert c._shed_retry(shed_once) == "g1/ok"
    assert calls["n"] == 2

    # Non-admission errors (wrong status, or EBUSY without a hint)
    # propagate immediately — no silent retry of a real failure.
    for err in (StatusError(2, "missing"), StatusError(16, "maxconn")):
        calls["n"] = 0

        def other():
            calls["n"] += 1
            raise err
        with pytest.raises(StatusError):
            c._shed_retry(other)
        assert calls["n"] == 1


def test_admission_retries_zero_disables_retry(monkeypatch):
    c = _client(admission_retries=0)
    monkeypatch.setattr(c, "_admission_wait",
                        lambda e: pytest.fail("waited with retries off"))
    calls = {"n": 0}

    def shed():
        calls["n"] += 1
        raise StatusError(16, "upload", retry_after_ms=25)
    with pytest.raises(StatusError):
        c._shed_retry(shed)
    assert calls["n"] == 1


def test_pool_release_clears_sticky_priority(monkeypatch):
    # A parked conn must not carry the previous borrower's QoS class
    # any more than its trace ctx — the next borrower may be an
    # untagged (per-opcode default) client.
    pool = _patched_pool(monkeypatch)
    conn = pool.acquire("127.0.0.1", 9)
    conn.priority = 4
    conn.trace_ctx = object()
    pool.release(conn)
    assert conn.priority is None and conn.trace_ctx is None


# ---------------------------------------------------------------------------
# connection pool: multiplexing cap + hygiene (ISSUE 18) — no daemons
# ---------------------------------------------------------------------------

class FakeConn:
    """Stands in for conn.Connection: the pool only touches host/port/
    broken/trace_ctx/priority/close, plus .sock through _quiet (patched
    out)."""

    def __init__(self, host="127.0.0.1", port=9, timeout=0.0):
        self.host = host
        self.port = port
        self.broken = False
        self.trace_ctx = None
        self.priority = None
        self.closed = False
        self.sock = None

    def close(self):
        self.closed = True


def _patched_pool(monkeypatch, **kw):
    monkeypatch.setattr("fastdfs_tpu.client.conn.Connection", FakeConn)
    monkeypatch.setattr("fastdfs_tpu.client.conn._quiet", lambda c: True)
    kw.setdefault("sweep_interval", 1e9)  # sweeps only when tests say so
    return ConnectionPool(**kw)


def test_pool_sweep_closes_idle_past_ttl(monkeypatch):
    pool = _patched_pool(monkeypatch, max_idle_seconds=10)
    conn = pool.acquire("127.0.0.1", 9)
    pool.release(conn)
    assert pool.idle_count() == 1
    # Not stale yet: a sweep inside the TTL keeps it parked.
    pool.sweep(now=time.monotonic() + 9)
    assert pool.idle_count() == 1 and not conn.closed
    # Past the TTL the sweep closes it — even though no caller ever
    # acquires this endpoint again (the leak sweeps exist to fix).
    pool.sweep(now=time.monotonic() + 11)
    assert pool.idle_count() == 0
    assert conn.closed
    assert pool.swept_idle == 1


def test_pool_sweep_drops_expired_dead_marks(monkeypatch):
    pool = _patched_pool(monkeypatch, dead_peer_cooldown=5)
    pool.mark_dead("10.0.0.1", 23000)
    pool.mark_dead("10.0.0.2", 23000)
    assert pool.dead_mark_count() == 2
    # Inside the cooldown the marks survive a sweep.
    pool.sweep(now=time.monotonic() + 4)
    assert pool.dead_mark_count() == 2
    # Past it they are dropped without anyone calling is_dead on the
    # departed endpoints.
    pool.sweep(now=time.monotonic() + 6)
    assert pool.dead_mark_count() == 0


def test_pool_cap_waits_then_overflows(monkeypatch):
    pool = _patched_pool(monkeypatch, max_conns_per_endpoint=1,
                         cap_wait_seconds=0.05)
    a = pool.acquire("127.0.0.1", 9)
    t0 = time.monotonic()
    b = pool.acquire("127.0.0.1", 9)  # cap held by a: wait, then overflow
    assert time.monotonic() - t0 >= 0.04
    assert a is not b
    assert pool.cap_overflows == 1
    assert pool.in_use_count("127.0.0.1", 9) == 2
    # A different endpoint is not throttled by this one's cap.
    pool.acquire("127.0.0.2", 9)
    assert pool.cap_overflows == 1


def test_pool_release_unblocks_capped_waiter(monkeypatch):
    pool = _patched_pool(monkeypatch, max_conns_per_endpoint=1,
                         cap_wait_seconds=30)
    a = pool.acquire("127.0.0.1", 9)
    got = {}

    def waiter():
        got["conn"] = pool.acquire("127.0.0.1", 9)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    assert "conn" not in got  # parked on the cap, not overflowing
    pool.release(a)
    t.join(timeout=10)
    assert not t.is_alive()
    # The waiter multiplexed onto the RELEASED socket — no new connect,
    # no overflow.
    assert got["conn"] is a
    assert pool.cap_overflows == 0
    assert pool.in_use_count() == 1


def test_pool_idle_total_evicts_globally_oldest(monkeypatch):
    pool = _patched_pool(monkeypatch, max_idle_total=2)
    conns = [pool.acquire("127.0.0.1", 9000 + i) for i in range(3)]
    for c in conns:
        pool.release(c)
    # The pool-wide cap closed the OLDEST parked conn (first released),
    # not the newest.
    assert pool.idle_count() == 2
    assert conns[0].closed
    assert not conns[1].closed and not conns[2].closed


def test_pool_double_release_never_wedges_the_cap(monkeypatch):
    pool = _patched_pool(monkeypatch, max_conns_per_endpoint=1,
                         cap_wait_seconds=0.05)
    a = pool.acquire("127.0.0.1", 9)
    pool.release(a)
    pool.release(a)  # buggy caller: must floor at zero, not go to -1
    assert pool.in_use_count() == 0
    # Accounting intact: the endpoint still hands out its one slot
    # instantly and enforces the cap for a second borrower.
    b = pool.acquire("127.0.0.1", 9)
    assert b is a
    pool.acquire("127.0.0.1", 9)
    assert pool.cap_overflows == 1
