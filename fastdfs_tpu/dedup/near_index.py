"""The near-duplicate index that lives on the device.

``DeviceNearIndex`` holds every MinHash signature the node has indexed in
one device array and answers "which stored files resemble this one" by
scanning all of it: the served twin of ``index.MinHashLSHIndex``, which
stays in the repo as the small-scale host reference and gives the same
answers on the same rows.

Layout.  The ``(capacity, num_perms)`` uint32 matrix is stored lane-major,
as ``(num_perms, capacity / LANES, LANES)``: a row of the index is a
column, so that a pass compares whole vectors of rows with one scalar of
the query and never reduces across lanes, and no dimension is padded to
the device's tile (a ``(capacity, 64)`` array would be, two-fold).
``live`` is the mask of rows that hold a signature and are not
tombstoned, in the same two minor dimensions.

The rule (``MinHashLSHIndex.query``'s): a row is a candidate iff it
shares one whole band (``bands`` bands of ``num_perms / bands`` lanes)
with the query; its score is the count of agreeing lanes over
``num_perms``; rows under ``min_similarity`` are dropped; the rest are
ordered by score descending, ties older row first, and the best ``top_k``
are returned.  Exact: every row is read by every pass.

A pass is one program, ``fdfs_near_scan``: one dispatch, one fetch.  It
reads the whole matrix once and reduces it to one bit a query a block of
``BLOCK`` rows: does the block hold a candidate (``ops/pallas_near_scan.py``:
a Pallas kernel on the TPU, the same function in ``jax.numpy`` elsewhere).
Candidates are rare, so the same program then picks the first
``RANK_BLOCKS`` nominated blocks (ascending), gathers only those, drops
what is not live or under the threshold, scores with ``band_scores`` and
ranks; what comes back is one array of the keys, the blocks and the count
of nominated blocks, so the chip never waits for the host between the
scan and the rank.  Where more blocks were nominated than that (rare: a
query's family spread over the index), the host fetches the scan's
bitmap and ranks the rest with ``fdfs_near_rank`` alone, ``RANK_BLOCKS``
at a time: the pass *spills* (``stats`` ``near_rank_spills``, the
``fdfs.near.scan`` span's ``spilled``).  The host merges the lists.  One
pass answers every query that was waiting when it started (``query``
joins a batch; the scanner thread is started by the first query, so a
process that is never asked compiles and runs none of this).

Writes go through one lock with the passes' dispatch: ``add`` writes its
column in place (a donated buffer) before it returns, and a pass
dispatched later reads it, so an acknowledged commit is seen by every
later query.  ``remove`` clears ``live`` on the device.

Capacity grows four-fold from ``MIN_CAPACITY`` rows (a growth allocates
the new array beside the old one and copies on the device), so a growth
is rare and the scan has few shapes; every shape is a compiled program.

A **base** (``base=(rows, seed)``) fills the first ``rows`` rows on the
device with seeded signatures, a counter hash of (seed, row, lane) in
plain integer arithmetic (``base_rows`` makes the same rows with NumPy);
their refs are ``base/<row>`` by rule and are held nowhere.  It is the
operator's way to see the node at the size it will hold before it holds
it (OPERATIONS.md, "Device memory").  A snapshot holds the base's spec
and the rows this process indexed itself, never the base.
"""

from __future__ import annotations

import functools
import json
import threading
import time
from typing import Any

import numpy as np

from fastdfs_tpu.dedup.index import (SIG_SPEC_VERSION, _atomic_savez,
                                     _npz_path)
from fastdfs_tpu.dedup.spans import new_acc, span
from fastdfs_tpu.ops.minhash import EMPTY
from fastdfs_tpu.ops.pallas_near_scan import BLOCK, LANES

MIN_CAPACITY = 4 * BLOCK  # 65,536 rows: 16 MiB on the device
GROWTH = 4
RANK_BLOCKS = 8           # nominated blocks one rank program gathers
MAX_TOP_K = 16            # the longest list a query may ask for
WRITE_ROWS = 4096         # rows of a bulk write (snapshot load)
QUERY_LADDER = (1, 2, 4, 8)   # queries a pass answers; each is a program
BASE_PREFIX = "base/"


def _fmix32(x):
    """murmur3's 32-bit finalizer; ``x`` a uint32 array of NumPy or JAX."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def base_rows(seed: int, start: int, stop: int, num_perms: int = 64
              ) -> np.ndarray:
    """Rows ``start`` to ``stop`` of the base of ``seed``, as the device
    makes them: ``(stop - start, num_perms)`` uint32."""
    with np.errstate(over="ignore"):
        counter = (np.arange(start, stop, dtype=np.uint32)[:, None]
                   * np.uint32(num_perms)
                   + np.arange(num_perms, dtype=np.uint32)[None, :])
        return _fmix32(counter ^ _fmix32(np.array(seed & 0xFFFFFFFF,
                                                  dtype=np.uint32)))


def min_count(min_similarity: float, num_perms: int) -> int:
    """The least count of agreeing lanes whose share of ``num_perms`` is
    at least ``min_similarity``."""
    c = max(0, int(min_similarity * num_perms))
    while c / num_perms < min_similarity:
        c += 1
    return c


def band_scores(sigs_t, queries, bands: int):
    """Lane-major scoring, shared by the index's rank program and by
    ``parallel/ingest_step.py``'s step 4: ``sigs_t`` ``(P, R)`` uint32 (a
    signature is a column), ``queries`` ``(Q, P)`` -> ``(counts (Q, R)
    int32, cand (Q, R) bool)``, the agreeing lanes of every row with every
    query and whether they share one whole band.  For the rows a pass has
    nominated or a shard holds, not for the whole index: the comparison is
    a ``(Q, P, R)`` temporary."""
    import jax.numpy as jnp

    n_q, perms = queries.shape
    by_band = (sigs_t[None] == queries[:, :, None]).reshape(
        n_q, bands, perms // bands, -1).sum(axis=2, dtype=jnp.int32)
    return by_band.sum(axis=1), (by_band == perms // bands).any(axis=1)


@functools.lru_cache(maxsize=None)
def _programs(bands: int, use_pallas: bool):
    """The jitted programs of an index of ``bands`` bands; shapes are
    taken from the arguments.  The device arrays are ``sigs_t (P,
    capacity / LANES, LANES)`` and ``live (capacity / LANES, LANES)``:
    row r is ``[:, r // LANES, r % LANES]``."""
    import jax
    import jax.numpy as jnp

    from fastdfs_tpu.ops import pallas_near_scan

    def fdfs_near_rank(sigs_t, live, asked, blocks):
        # asked: DeviceNearIndex._asked's; blocks: ascending block
        # numbers, -1 for none.  Rows from the limit on were written after
        # the pass was dispatched and are not its own.
        perms = sigs_t.shape[0]
        queries = asked[:, :perms]
        least = asked[:, perms].astype(jnp.int32)
        limit = asked[0, perms + 1].astype(jnp.int32)
        at = jnp.maximum(blocks, 0)
        n = blocks.shape[0] * BLOCK
        # one dynamic slice a block (a gather would copy the matrix)
        sub = BLOCK // LANES
        rows = jnp.concatenate(
            [jax.lax.dynamic_slice_in_dim(sigs_t, at[h] * sub, sub, axis=1)
             for h in range(blocks.shape[0])], axis=1).reshape(perms, n)
        alive = (jnp.stack(
            [jax.lax.dynamic_slice_in_dim(live, at[h] * sub, sub, axis=0)
             for h in range(blocks.shape[0])]).reshape(-1, BLOCK)
                 & (blocks >= 0)[:, None]
                 & (at[:, None] * BLOCK + jnp.arange(BLOCK)[None, :] < limit)
                 ).reshape(n)
        counts, cand = band_scores(rows, queries, bands)
        hit = cand & (counts >= least[:, None]) & alive[None, :]
        # score first, then the older row: positions ascend with the rows
        key = jnp.where(hit, counts * n + (n - 1 - jnp.arange(n))[None, :],
                        -1)
        return jax.lax.top_k(key, MAX_TOP_K)[0]

    def fdfs_near_scan(sigs_t, live, asked):
        # the whole pass: the scan, its first RANK_BLOCKS nominated blocks
        # found on the device, and their rank; one array comes back
        scan = (pallas_near_scan.near_scan_pallas if use_pallas
                else pallas_near_scan.near_scan_xla)
        blocks = scan(sigs_t, asked[:, :sigs_t.shape[0]], bands=bands)
        nominated = blocks.any(axis=0)
        (first,) = jnp.nonzero(nominated, size=RANK_BLOCKS, fill_value=-1)
        first = first.astype(jnp.int32)
        keys = fdfs_near_rank(sigs_t, live, asked, first)
        return jnp.concatenate(
            [keys.reshape(-1), first,
             nominated.sum(dtype=jnp.int32)[None]]), blocks

    def fdfs_near_insert(sigs_t, live, col, row, alive):
        at = (row // LANES, row % LANES)
        return (jax.lax.dynamic_update_slice(sigs_t, col[:, None, None],
                                             (0, *at)),
                jax.lax.dynamic_update_slice(live, alive.reshape(1, 1), at))

    def fdfs_near_load(sigs_t, live, cols, rows):
        # rows: padded with an index past the end, which is dropped
        sub, lane = rows // LANES, rows % LANES
        return (sigs_t.at[:, sub, lane].set(cols, mode="drop"),
                live.at[sub, lane].set(True, mode="drop"))

    def fdfs_near_kill(live, rows):
        return live.at[rows // LANES, rows % LANES].set(False, mode="drop")

    def fdfs_near_grow(sigs_t, live, *, subs):
        return (jax.lax.dynamic_update_slice(
                    jnp.zeros((sigs_t.shape[0], subs, LANES), sigs_t.dtype),
                    sigs_t, (0, 0, 0)),
                jax.lax.dynamic_update_slice(
                    jnp.zeros((subs, LANES), bool), live, (0, 0)))

    def fdfs_near_base(seed, *, perms, subs, rows):
        shape = (perms, subs, LANES)
        row = (jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
               * np.uint32(LANES)
               + jax.lax.broadcasted_iota(jnp.uint32, shape, 2))
        lane = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
        made = _fmix32((row * np.uint32(perms) + lane) ^ _fmix32(seed))
        held = row < np.uint32(rows)
        return jnp.where(held, made, np.uint32(0)), held[0]

    return {
        "scan": jax.jit(fdfs_near_scan),
        "rank": jax.jit(fdfs_near_rank),
        "insert": jax.jit(fdfs_near_insert, donate_argnums=(0, 1)),
        "load": jax.jit(fdfs_near_load, donate_argnums=(0, 1)),
        "kill": jax.jit(fdfs_near_kill, donate_argnums=(0,)),
        "grow": jax.jit(fdfs_near_grow, static_argnames=("subs",)),
        "base": jax.jit(fdfs_near_base,
                        static_argnames=("perms", "subs", "rows")),
    }


class _Waiting:
    """One query in the batch queue."""

    __slots__ = ("sig", "top_k", "least", "acc", "started", "done",
                 "result", "error")

    def __init__(self, sig, top_k: int, least: int, acc: dict) -> None:
        self.sig, self.top_k, self.least, self.acc = sig, top_k, least, acc
        self.started = threading.Event()
        self.done = threading.Event()
        self.result: list | None = None
        self.error: BaseException | None = None


class DeviceNearIndex:
    """See the module's docstring.  ``add`` / ``remove`` / ``query`` /
    ``signature_of`` / ``save`` / ``load`` are ``MinHashLSHIndex``'s."""

    def __init__(self, num_perms: int = 64, bands: int = 16,
                 base: tuple[int, int] | None = None,
                 use_pallas: bool = False) -> None:
        if num_perms % bands:
            raise ValueError(f"bands {bands} must divide num_perms {num_perms}")
        self.num_perms, self.bands = num_perms, bands
        self.use_pallas = use_pallas
        self._programs = _programs(bands, use_pallas)
        self.base_rows, self.base_seed = base or (0, 0)
        if not 0 <= self.base_rows * num_perms < 1 << 32:
            # the base's counter (row * num_perms + lane) is 32 bits
            raise ValueError(f"near base of {self.base_rows} rows")
        # The host's tables, for the rows this process indexed itself
        # (own row i is device row base_rows + i).
        self._own = np.zeros((0, num_perms), np.uint32)
        self._refs: list[Any] = []
        self._ids_by_ref: dict[Any, list[int]] = {}
        self._base_dead: set[int] = set()
        self._sigs_t = self._live = None     # device arrays, made on demand
        self.capacity = 0
        # Every touch of the device arrays (a write donates them) and of
        # the host tables happens under this lock; a pass holds it for its
        # dispatch only, never for its wait.
        self._lock = threading.Lock()
        self.counters = {"near_queries": 0, "near_scans": 0,
                         "near_scan_us": 0, "near_rank_spills": 0,
                         "near_inserts": 0, "near_removed": 0}
        self._queue: list[_Waiting] = []
        self._wake = threading.Condition()
        self._scanner: threading.Thread | None = None

    # -- the device arrays ----------------------------------------------------

    @staticmethod
    def _capacity_for(rows: int) -> int:
        return -(-rows // MIN_CAPACITY) * MIN_CAPACITY

    def _allocate(self, capacity: int) -> None:
        """The arrays at ``capacity``: a fresh index's (with its base) or
        the held rows copied into larger ones."""
        prog = self._programs
        subs = capacity // LANES
        if self._sigs_t is None:
            self._sigs_t, self._live = prog["base"](
                np.uint32(self.base_seed & 0xFFFFFFFF), perms=self.num_perms,
                subs=subs, rows=self.base_rows)
        else:
            self._sigs_t, self._live = prog["grow"](
                self._sigs_t, self._live, subs=subs)
        self.capacity = capacity

    def _room_for(self, rows: int) -> None:
        """Capacity for ``rows`` more own rows.  The arrays are made by
        the first call: a base with an eighth of itself to spare, or
        ``MIN_CAPACITY`` rows."""
        need = self.base_rows + len(self._refs) + rows
        if need <= self.capacity:
            return
        capacity = self.capacity or max(
            MIN_CAPACITY, self.base_rows + self.base_rows // 8)
        while capacity < need:
            capacity *= GROWTH
        self._allocate(self._capacity_for(capacity))

    def _load_rows(self, start: int, rows: np.ndarray) -> None:
        """Many rows at once (a snapshot's), ``WRITE_ROWS`` a program."""
        prog = self._programs["load"]
        for lo in range(0, len(rows), WRITE_ROWS):
            part = rows[lo:lo + WRITE_ROWS]
            cols = np.zeros((self.num_perms, WRITE_ROWS), np.uint32)
            cols[:, :len(part)] = part.T
            at = np.full(WRITE_ROWS, self.capacity, np.int32)
            at[:len(part)] = np.arange(start + lo, start + lo + len(part))
            self._sigs_t, self._live = prog(self._sigs_t, self._live,
                                            cols, at)

    def warmup(self) -> None:
        """Make the arrays and compile the one-row write at the capacity
        in force, so no commit pays a trace; with a base, also every scan
        program (a node that holds a base is there to be asked)."""
        with self._lock:
            self._room_for(1)
            # a dead column past the rows that are held: nothing changes
            self._sigs_t, self._live = self._programs["insert"](
                self._sigs_t, self._live,
                np.zeros(self.num_perms, np.uint32),
                np.int32(self.base_rows + len(self._refs)), np.bool_(False))
        if self.base_rows:
            probe = base_rows(self.base_seed, 0, 1, self.num_perms)[0]
            for q in QUERY_LADDER:
                batch = [_Waiting(probe, 1, self.num_perms + 1, new_acc())
                         for _ in range(q)]
                self._pass(batch, counted=False)
                # and the spill's rank program, which this pass needs not
                self._rank(self._asked(batch, 0),
                           np.full(RANK_BLOCKS, -1, np.int32))

    # -- MinHashLSHIndex's surface -----------------------------------------------

    def __len__(self) -> int:
        """Rows this process indexed itself (tombstones included)."""
        return len(self._refs)

    def add(self, sig: np.ndarray, ref: Any, acc: dict | None = None) -> int:
        """Insert; the own row's number, or -1 for an all-``EMPTY``
        signature (it carries no similarity information).  The row is on
        the device's queue, ahead of any later pass, when this returns."""
        sig = np.asarray(sig, dtype=np.uint32)
        if sig.shape != (self.num_perms,):
            raise ValueError(f"signature shape {sig.shape} != ({self.num_perms},)")
        if (sig == EMPTY).all():
            return -1
        with span("fdfs.near.insert", acc if acc is not None else new_acc()), \
                self._lock:
            item = len(self._refs)
            self._room_for(1)
            self._sigs_t, self._live = self._programs["insert"](
                self._sigs_t, self._live, sig,
                np.int32(self.base_rows + item), np.bool_(True))
            if item == len(self._own):
                grown = np.zeros((max(1024, 2 * item), self.num_perms),
                                 np.uint32)
                grown[:item] = self._own
                self._own = grown
            self._own[item] = sig
            self._refs.append(ref)
            self._ids_by_ref.setdefault(ref, []).append(item)
            self.counters["near_inserts"] += 1
        return item

    def _base_row_of(self, ref: Any) -> int | None:
        if isinstance(ref, str) and ref.startswith(BASE_PREFIX):
            tail = ref[len(BASE_PREFIX):]
            if tail.isdigit() and int(tail) < self.base_rows:
                return int(tail)
        return None

    def remove(self, ref: Any) -> int:
        """Tombstone every row that carries ``ref``, on the device too; no
        later query returns them.  Returns the number of rows removed."""
        with self._lock:
            ids = self._ids_by_ref.pop(ref, None) or []
            rows = [self.base_rows + i for i in ids]
            for i in ids:
                self._refs[i] = None
            row = self._base_row_of(ref)
            if row is not None and row not in self._base_dead:
                self._base_dead.add(row)
                rows.append(row)
            if rows:
                self._room_for(0)
                self._kill(rows)
            self.counters["near_removed"] += len(rows)
        return len(rows)

    def _kill(self, rows: list[int]) -> None:
        # padded to a power of two with an index past the end (dropped)
        width = 1 << (len(rows) - 1).bit_length()
        padded = np.full(width, self.capacity, np.int32)
        padded[:len(rows)] = rows
        self._live = self._programs["kill"](self._live, padded)

    def signature_of(self, ref: Any) -> np.ndarray | None:
        """The latest signature stored under ``ref``; None when it is
        unindexed or removed.  A base row's is made again from its rule."""
        with self._lock:
            ids = self._ids_by_ref.get(ref)
            if ids:
                return self._own[ids[-1]].copy()
            row = self._base_row_of(ref)
        if row is None or row in self._base_dead:
            return None
        return base_rows(self.base_seed, row, row + 1, self.num_perms)[0]

    def query(self, sig: np.ndarray, top_k: int = 5,
              min_similarity: float = 0.5, acc: dict | None = None
              ) -> list[tuple[Any, float]]:
        """The best ``top_k`` (ref, score) under the module's rule.  Joins
        the pass that starts next: one pass answers every query that is
        waiting when it starts."""
        sig = np.asarray(sig, dtype=np.uint32)
        if top_k > MAX_TOP_K:
            raise ValueError(f"top_k {top_k}: the index ranks {MAX_TOP_K}")
        if (sig == EMPTY).all() or top_k < 1 or not (
                self.base_rows or self._refs):
            return []
        me = _Waiting(sig, top_k, min_count(min_similarity, self.num_perms),
                      acc if acc is not None else new_acc())
        with self._wake:
            if self._scanner is None:
                self._scanner = threading.Thread(
                    target=self._scan_loop, name="fdfs-near-scan", daemon=True)
                self._scanner.start()
            self._queue.append(me)
            self._wake.notify()
        with span("fdfs.near.queue_wait", me.acc):
            me.started.wait()
        me.done.wait()
        if me.error is not None:
            raise me.error
        return me.result

    # -- the pass ---------------------------------------------------------------

    def _scan_loop(self) -> None:
        while True:
            with self._wake:
                while not self._queue:
                    self._wake.wait()
                batch = self._queue[:QUERY_LADDER[-1]]
                del self._queue[:len(batch)]
            for w in batch:
                w.started.set()
            try:
                self._pass(batch)
            except Exception as e:  # noqa: BLE001 — the askers raise it
                for w in batch:
                    w.error = e
            for w in batch:
                w.done.set()

    def _asked(self, batch: list[_Waiting], held: int) -> np.ndarray:
        """What a pass's programs take of ``batch``, in one array (one
        transfer): ``(width, P + 2)`` uint32 at the batch's
        ``QUERY_LADDER`` width, a query's lanes, then its least count,
        then ``held`` (the rows the pass may return lie under it).  A
        padding query asks for more lanes than there are: no hit."""
        width = next(q for q in QUERY_LADDER if q >= len(batch))
        perms = self.num_perms
        asked = np.empty((width, perms + 2), np.uint32)
        asked[:, :perms] = batch[0].sig
        asked[:, perms] = perms + 1
        for i, w in enumerate(batch):
            asked[i, :perms] = w.sig
            asked[i, perms] = w.least
        asked[:, perms + 1] = held
        return asked

    def _rank(self, asked: np.ndarray, chunk: np.ndarray):
        """The rank program alone, over the blocks of ``chunk``."""
        with self._lock:
            # a write since the pass donated the arrays it read; the ones
            # in force hold every row the pass saw
            return self._programs["rank"](self._sigs_t, self._live, asked,
                                          chunk)

    def _pass(self, batch: list[_Waiting], counted: bool = True) -> None:
        """One pass for ``batch``: fills in every ``result``."""
        t0 = time.perf_counter_ns()
        with span("fdfs.near.scan", batch[0].acc,
                  queries=len(batch)) as scan:
            with self._lock:
                # the program reads every row acknowledged before this
                self._room_for(0)
                held = self.base_rows + len(self._refs)
                asked = self._asked(batch, held)
                out, blocks = self._programs["scan"](self._sigs_t,
                                                     self._live, asked)
            out = np.asarray(out)
            n_keys = len(asked) * MAX_TOP_K
            first = out[n_keys:n_keys + RANK_BLOCKS]
            keys = [(first, out[:n_keys].reshape(-1, MAX_TOP_K))]
            spilled = out[-1] > RANK_BLOCKS
            if spilled:
                # more nominated blocks than the program ranked: the rest
                # by the rank program, RANK_BLOCKS at a time
                hit_blocks = np.flatnonzero(np.asarray(blocks).any(axis=0))
                for lo in range(RANK_BLOCKS, len(hit_blocks), RANK_BLOCKS):
                    chunk = np.full(RANK_BLOCKS, -1, np.int32)
                    part = hit_blocks[lo:lo + RANK_BLOCKS]
                    chunk[:len(part)] = part
                    keys.append((chunk, np.asarray(self._rank(asked, chunk))))
            scan.note(rows=held, spilled=int(spilled))
        with self._lock:
            if counted:
                self.counters["near_scans"] += 1
                self.counters["near_queries"] += len(batch)
                self.counters["near_rank_spills"] += int(spilled)
                self.counters["near_scan_us"] += (
                    time.perf_counter_ns() - t0) // 1000
        span_rows = RANK_BLOCKS * BLOCK
        for i, w in enumerate(batch):
            with span("fdfs.near.rank", w.acc):
                found = []                    # (-count, row)
                for chunk, got in keys:
                    for key in got[i]:
                        if key < 0:
                            break
                        count, back = divmod(int(key), span_rows)
                        at = span_rows - 1 - back
                        found.append((-count, int(chunk[at // BLOCK])
                                      * BLOCK + at % BLOCK))
                found.sort()
                w.result = self._refs_of(found[:w.top_k])

    def _refs_of(self, found: list[tuple[int, int]]
                 ) -> list[tuple[Any, float]]:
        out = []
        with self._lock:
            for neg, row in found:
                ref = (f"{BASE_PREFIX}{row}" if row < self.base_rows
                       else self._refs[row - self.base_rows])
                if ref is not None:      # removed since the pass: not returned
                    out.append((ref, -neg / self.num_perms))
        return out

    # -- what `stats` reports -----------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {**self.counters,
                    "near_rows": self.base_rows + len(self._refs),
                    "near_base_rows": self.base_rows,
                    "near_resident_bytes":
                        self.capacity * (4 * self.num_perms + 1)}

    # -- persistence ------------------------------------------------------------------

    def save(self, path: str) -> None:
        """The base's spec and the live rows this process indexed, in
        ``MinHashLSHIndex.save``'s keys; nothing is read from the device."""
        with self._lock:
            alive = [i for i, r in enumerate(self._refs) if r is not None]
            sigs = self._own[alive]
            refs = np.array([json.dumps(self._refs[i]) for i in alive],
                            dtype=object)
            base_dead = np.array(sorted(self._base_dead), np.int64)
        _atomic_savez(path, sigs=sigs, refs=refs, num_perms=self.num_perms,
                      bands=self.bands, sig_spec=SIG_SPEC_VERSION,
                      base_rows=self.base_rows, base_seed=self.base_seed,
                      base_dead=base_dead)

    @classmethod
    def load(cls, path: str, base: tuple[int, int] | None = None,
             use_pallas: bool = False) -> "DeviceNearIndex":
        """The index a snapshot describes.  ``base`` is the spec this
        process runs under: a snapshot written under another one (or
        another signature spec) is refused with ``ValueError``."""
        data = np.load(_npz_path(path), allow_pickle=True)
        spec = int(data["sig_spec"]) if "sig_spec" in data else 1
        if spec != SIG_SPEC_VERSION:
            raise ValueError(
                f"near-dup index snapshot {path!r} holds spec-v{spec} "
                f"signatures, this build computes spec-v{SIG_SPEC_VERSION}; "
                "the sets are not comparable — delete the snapshot and "
                "re-ingest (exact dedup state is unaffected)")
        held = ((int(data["base_rows"]), int(data["base_seed"]))
                if "base_rows" in data else (0, 0))
        if held != (base or (0, 0)):
            raise ValueError(
                f"near-dup index snapshot {path!r} was written over the "
                f"base {held[0]}:{held[1]}, this process runs "
                f"{'%d:%d' % base if base else 'without one'}")
        idx = cls(int(data["num_perms"]), int(data["bands"]), base,
                  use_pallas)
        sigs = np.asarray(data["sigs"], dtype=np.uint32)
        refs = [json.loads(str(r)) for r in data["refs"]]
        keep = [i for i, r in enumerate(refs) if r is not None]
        with idx._lock:
            idx._own = np.ascontiguousarray(sigs[keep])
            idx._refs = [refs[i] for i in keep]
            for i, ref in enumerate(idx._refs):
                idx._ids_by_ref.setdefault(ref, []).append(i)
            if idx._refs:
                idx._room_for(0)
                idx._load_rows(idx.base_rows, idx._own)
            dead = [int(r) for r in data["base_dead"]] \
                if "base_dead" in data else []
            if dead:
                idx._base_dead = set(dead)
                idx._room_for(0)
                idx._kill(dead)
        return idx
