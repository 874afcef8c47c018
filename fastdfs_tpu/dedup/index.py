"""Digest indexes: exact (SHA1) and near-dup (MinHash + LSH banding).

The exact index is the dedup verdict authority; the LSH index serves the
tracker-side near-duplicate queries (north star: "tracker's file-id index
backed by a jax.numpy cosine/MinHash similarity search").  Both snapshot to
disk — the new stateful component SURVEY.md §5 says checkpoint/resume must
cover (the reference's restart-safety is binlogs + ``.dat`` files; the
dedup index gets the same treatment).
"""

from __future__ import annotations

import json
import os
from typing import Any, Sequence

import numpy as np

from fastdfs_tpu.dedup.spans import new_acc, span
from fastdfs_tpu.ops.minhash import EMPTY

# Bumped whenever the signature spec changes (v2 = the survivor sketch,
# round 3); snapshots carry it so a stale index fails loudly instead of
# silently scoring noise against incompatible signatures.
SIG_SPEC_VERSION = 2


# Sentinel offset meaning "the ref is the carrier object itself, not a
# [carrier, offset] pair" — kept for API generality; production refs are
# always [file_ref, offset].
_OFF_BARE = -(1 << 62)

# Snapshot format version for the exact index (v2 = columnar arrays;
# v1 = flat digest bytes + per-entry json refs).  load() reads both.
_EXACT_SPEC = 2

# The delta is folded into the base once it holds max(this, base / 4) rows.
_MERGE_ROWS = 65536

# A batch under this many digests is probed and kept one digest at a time
# (a dict probe, a scalar search a run): numpy lets the interpreter go in
# a search or sort of a few dozen keys or more, and under the sidecar's
# lock each such hand-off waits out the other busy threads' slices.
_ONE_BY_ONE = 256


def _prefix_keys(dig: np.ndarray) -> np.ndarray:
    """The first 8 bytes of each ``S20`` digest as one big-endian
    ``uint64``: the order of the ``S20`` column, so that a probe compares
    integers and only a shared prefix needs the whole digest."""
    raw = np.ascontiguousarray(dig).view(np.uint8).reshape(-1, 20)
    return raw[:, :8].copy().view(">u8").ravel().astype(np.uint64)


class _Run:
    """Rows sorted by digest: the ``S20`` digests, their prefix keys (the
    same order), carrier ids, offsets and tombstones.  No digest is in a
    run twice."""

    __slots__ = ("dig", "key", "cid", "off", "dead")

    def __init__(self, dig: np.ndarray, cid: np.ndarray, off: np.ndarray,
                 key: np.ndarray | None = None) -> None:
        self.dig = dig
        self.key = _prefix_keys(dig) if key is None else key
        self.cid = cid
        self.off = off
        self.dead = np.zeros(len(dig), dtype=bool)

    def __len__(self) -> int:
        return len(self.dig)

    def holds(self, digest: bytes, k: np.uint64) -> bool:
        """Whether one digest (``k``: its prefix key) is live here, by a
        scalar search that keeps the interpreter."""
        n = len(self.dig)
        i = int(self.key.searchsorted(k))
        while i < n and self.key[i] == k:
            if self.dig[i:i + 1].tobytes() == digest:   # all 20 bytes
                return not self.dead[i]
            i += 1
        return False

    def find(self, q: np.ndarray, qkey: np.ndarray) -> np.ndarray:
        """The row of each query digest's live entry here, or -1.  ``q``
        is an ``S20`` array: only S20-to-S20 comparison gets NUL-padding
        semantics, so the ~1/256 SHA1 digests ending in 0x00 still match."""
        n = len(self.dig)
        if n == 0:
            return np.full(len(q), -1, dtype=np.intp)
        row = np.searchsorted(self.key, qkey)
        np.minimum(row, n - 1, out=row)
        hit = self.dig[row] == q
        # a prefix this run holds more than once: the digests place it
        tie = ~hit & (self.key[row] == qkey)
        if tie.any():
            j = np.flatnonzero(tie)
            r = np.minimum(np.searchsorted(self.dig, q[j]), n - 1)
            row[j] = r
            hit[j] = self.dig[r] == q[j]
        hit &= ~self.dead[row]
        return np.where(hit, row, -1)

    @classmethod
    def merged(cls, runs: Sequence["_Run"]) -> "_Run":
        """One run of the live rows of ``runs`` (a digest is live in one
        of them at most)."""
        cols = [[], [], [], []]
        for r in runs:
            live = ~r.dead if r.dead.any() else slice(None)
            for col, a in zip(cols, (r.dig, r.key, r.cid, r.off)):
                col.append(a[live])
        dig, key, cid, off = (np.concatenate(c) for c in cols)
        order = np.argsort(key, kind="stable")
        sk = key[order]
        if (sk[1:] == sk[:-1]).any():   # a shared prefix: sort the digests
            order = np.argsort(dig, kind="stable")
        return cls(dig[order], cid[order], off[order], key[order])


class ExactDigestIndex:
    """digest bytes → ``[carrier, offset]`` ref (chunk locator / file id),
    engineered for tens of millions of entries.

    A plain ``dict[bytes, list]`` costs ~200 B/entry — config 5's nominal
    scale (~62M chunks) would need >12 GB of pure bookkeeping.  Instead:
    an LSM-flavored layout of sorted runs (``_Run``: an ``S20`` digest
    column, its first 8 bytes as ``uint64`` keys, parallel ``int32``
    carrier-id / ``int64`` offset columns).  The BASE is one run; the
    DELTA is a few small ones, one appended per inserted batch, the last
    two merged while they are within twice each other's size (so at most
    ~log2 of them), plus a dict of the digests that batches under
    ``_ONE_BY_ONE`` added, and all of it is folded into the base when it
    reaches a quarter of it.  ~41 B/entry steady-state, a large batch of
    inserts or lookups is a few ``np.searchsorted`` over integer keys with
    no Python per digest, and snapshots are raw column dumps (SHA1
    digests are incompressible — no zlib pass).

    Carrier objects (file ids) are interned in a side table, so the per
    entry cost is independent of file-id length.  Removals tombstone
    rows, compacted at the next merge.
    """

    def __init__(self) -> None:
        self._base = _Run(np.empty(0, dtype="S20"),
                          np.empty(0, dtype=np.int32),
                          np.empty(0, dtype=np.int64))
        self._delta: list[_Run] = []    # newest last
        self._small: dict[bytes, tuple[int, int]] = {}  # dig -> (cid, off)
        self._delta_rows = 0            # rows in the delta, tombstones too
        self._carriers: list[Any] = []
        self._carrier_ids: dict[Any, int] = {}
        self._len = 0
        self.insert_batches = self.inserted = self.merges = 0

    def __len__(self) -> int:
        return self._len

    def stats(self) -> dict:
        """Counters for the sidecar's ``stats`` reply: inserted batches
        (one a commit), the digests they added, delta → base merges."""
        return {"exact_insert_batches": self.insert_batches,
                "exact_inserted": self.inserted,
                "exact_merges": self.merges}

    # -- internals ---------------------------------------------------------

    def _cid(self, carrier: Any) -> int:
        i = self._carrier_ids.get(carrier)
        if i is None:
            i = len(self._carriers)
            self._carriers.append(carrier)
            self._carrier_ids[carrier] = i
        return i

    @staticmethod
    def _decompose(ref: Any) -> tuple[Any, int]:
        if (isinstance(ref, (list, tuple)) and len(ref) == 2
                and isinstance(ref[1], (int, np.integer))):
            return ref[0], int(ref[1])
        return ref, _OFF_BARE

    def _compose(self, cid: int, off: int) -> Any:
        c = self._carriers[cid]
        return c if off == _OFF_BARE else [c, off]

    def _runs(self) -> list[_Run]:
        return [self._base, *self._delta]

    def _find(self, q: np.ndarray, qkey: np.ndarray | None = None
              ) -> tuple[list[_Run], np.ndarray, np.ndarray]:
        """For each ``S20`` query digest (``qkey``: its prefix keys, if
        known): which of the runs holds it live (-1: none; at most one
        does) and its row there."""
        runs = self._runs()
        if qkey is None:
            qkey = _prefix_keys(q)
        where = np.full(len(q), -1, dtype=np.intp)
        row = np.full(len(q), -1, dtype=np.intp)
        for i, run in enumerate(runs):
            r = run.find(q, qkey)
            hit = r >= 0
            where[hit] = i
            row[hit] = r[hit]
        return runs, where, row

    def _merge(self, acc: dict | None = None) -> None:
        """Fold the delta into the base (and compact tombstones)."""
        with span("fdfs.exact.merge", new_acc() if acc is None else acc) as s:
            runs = self._runs()
            if self._small:     # unsorted: merged() sorts what it is given
                n, vals = len(self._small), self._small.values()
                runs.append(_Run(
                    np.fromiter(self._small.keys(), dtype="S20", count=n),
                    np.fromiter((v[0] for v in vals), dtype=np.int32,
                                count=n),
                    np.fromiter((v[1] for v in vals), dtype=np.int64,
                                count=n)))
            self._base = _Run.merged(runs)
            self._delta = []
            self._small = {}
            self._delta_rows = 0
            self._compact_carriers()
            s.note(rows=len(self._base))
        self.merges += 1

    def _compact_carriers(self) -> None:
        """Drop forgotten (None-slotted) carriers and remap the base
        carrier column — without this, create/forget churn leaks every
        dead file-id string into RAM and every snapshot forever.  Only
        runs on merge, when the delta is empty (its cids would otherwise
        need remapping too)."""
        if not any(c is None for c in self._carriers):
            return
        base = self._base
        used = np.unique(base.cid) if len(base) \
            else np.empty(0, dtype=np.int32)
        remap = np.full(len(self._carriers), -1, dtype=np.int32)
        remap[used] = np.arange(len(used), dtype=np.int32)
        base.cid = remap[base.cid]
        self._carriers = [self._carriers[int(c)] for c in used]
        self._carrier_ids = {}
        for i, c in enumerate(self._carriers):
            try:
                self._carrier_ids[c] = i
            except TypeError:
                pass  # unhashable carrier (load() tolerates them too)

    def _settle(self, acc: dict | None) -> None:
        """After a run is appended: merge the last two runs while they are
        within twice each other's size, and the delta into the base once
        it holds a quarter of it."""
        d = self._delta
        while len(d) > 1 and len(d[-2]) <= 2 * len(d[-1]):
            d[-2:] = [_Run.merged(d[-2:])]
        self._delta_rows = sum(map(len, d)) + len(self._small)
        if self._delta_rows >= max(_MERGE_ROWS, len(self._base) // 4):
            self._merge(acc)

    # -- API ---------------------------------------------------------------

    def lookup(self, digest: bytes):
        return self.lookup_batch([digest])[0]

    def lookup_batch(self, digests: Sequence[bytes]) -> list[Any]:
        """One vectorized probe of every run for the whole batch (the TPU
        engine judges chunks hundreds at a time)."""
        out: list[Any] = [None] * len(digests)
        if not digests:
            return out
        runs, where, row = self._find(np.array(list(digests), dtype="S20"))
        for j in np.flatnonzero(where >= 0):
            run, i = runs[where[j]], row[j]
            out[j] = self._compose(int(run.cid[i]), int(run.off[i]))
        if self._small:
            for j, d in enumerate(digests):
                v = self._small.get(d)
                if v is not None:
                    out[j] = self._compose(v[0], v[1])
        return out

    def insert(self, digest: bytes, ref: Any) -> bool:
        """Insert if absent; returns True when this digest was new."""
        carrier, off = self._decompose(ref)
        return self.insert_batch(digest, carrier, [off]) == 1

    def insert_batch(self, digests, carrier: Any, offsets,
                     acc: dict | None = None) -> int:
        """``insert(digests[i], [carrier, offsets[i]])`` for every i in
        order, as one probe (under ``_ONE_BY_ONE`` digests, one at a time):
        ``digests`` is 20·n raw bytes (any buffer), ``offsets`` n
        integers.  A digest already live keeps its ref and,
        within the batch, the first occurrence wins.  Returns how many
        were new.  A merge it sets off is a ``fdfs.exact.merge`` span in
        ``acc``."""
        q = np.frombuffer(digests, dtype="S20")
        self.insert_batches += 1
        if len(q) < _ONE_BY_ONE:
            return self._insert_one_by_one(bytes(digests), carrier, offsets,
                                           acc)
        # each digest once, at its first occurrence, in key order
        qkey, first = np.unique(_prefix_keys(q), return_index=True)
        if len(first) < len(q):     # a shared prefix: the digests decide
            _, first = np.unique(q, return_index=True)
            qkey = _prefix_keys(q[first])
        q = q[first]
        _, where, _ = self._find(q, qkey)
        new = np.flatnonzero(where < 0)
        if self._small:
            raw = q.tobytes()
            new = new[[raw[i * 20:(i + 1) * 20] not in self._small
                       for i in new]]
        if len(new):
            run = _Run(q[new], np.full(len(new), self._cid(carrier),
                                       dtype=np.int32),
                       np.asarray(offsets, dtype=np.int64)[first[new]],
                       qkey[new])
            self._delta.append(run)
            self._len += len(new)
            self.inserted += len(new)
            self._settle(acc)
        return len(new)

    def _insert_one_by_one(self, raw: bytes, carrier: Any, offsets,
                           acc: dict | None) -> int:
        """``insert_batch`` for a batch under ``_ONE_BY_ONE``: a dict probe
        and a scalar search of each run a digest, the new ones into
        ``_small``."""
        runs, small, cid, new = self._runs(), self._small, None, 0
        for i, off in enumerate(offsets):
            d = raw[i * 20:(i + 1) * 20]
            if d in small:
                continue
            k = np.uint64(int.from_bytes(d[:8], "big"))
            if any(run.holds(d, k) for run in runs):
                continue
            if cid is None:
                cid = self._cid(carrier)
            small[d] = (cid, int(off))
            new += 1
        if new:
            self._len += new
            self.inserted += new
            self._settle(acc)
        return new

    def remove(self, digest: bytes) -> bool:
        if self._small.pop(digest, None) is not None:
            self._len -= 1
            return True
        runs, where, row = self._find(np.array([digest], dtype="S20"))
        if where[0] < 0:
            return False
        runs[where[0]].dead[row[0]] = True
        self._len -= 1
        return True

    def items(self):
        """Live (digest, ref) pairs — delta first, then base.  Digests are
        sliced from the column's raw bytes: numpy ``S20`` scalars strip
        trailing NULs on extraction, which would silently shorten ~1/256
        SHA1 digests for byte-equality consumers."""
        for d, (cid, off) in self._small.items():
            yield d, self._compose(cid, off)
        for run in [*self._delta, self._base]:
            raw = run.dig.tobytes()
            for i in np.flatnonzero(~run.dead):
                yield raw[i * 20:(i + 1) * 20], self._compose(
                    int(run.cid[i]), int(run.off[i]))

    def remove_by_carrier(self, carrier: Any) -> int:
        """Tombstone every live entry attributed to ``carrier`` (a deleted
        file id) — one vectorized mask over each run's carrier column, so
        `forget` needs no per-file side table of digest lists (which
        would reintroduce the per-entry object overhead this columnar
        layout exists to avoid).  Returns the number removed."""
        cid = self._carrier_ids.get(carrier)
        if cid is None:
            return 0
        dead_small = [d for d, v in self._small.items() if v[0] == cid]
        for d in dead_small:
            del self._small[d]
        n = len(dead_small)
        for run in self._runs():
            hit = (run.cid == cid) & ~run.dead
            run.dead |= hit
            n += int(hit.sum())
        self._len -= n
        # Release the interned id now (the string itself at the next
        # merge): churned file ids must not accumulate in the carrier
        # table or its snapshots.
        self._carriers[cid] = None
        del self._carrier_ids[carrier]
        return n

    # -- persistence (checkpoint/resume parity; SURVEY.md §5) -------------

    def save(self, path: str) -> None:
        self._merge()  # snapshot = one sorted columnar base
        _atomic_savez(
            path, compress=False,  # SHA1 columns are incompressible
            digests=self._base.dig.view(np.uint8),
            carrier_idx=self._base.cid, offsets=self._base.off,
            carriers=np.array([json.dumps(c) for c in self._carriers],
                              dtype=object),
            exact_spec=_EXACT_SPEC)

    @classmethod
    def load(cls, path: str) -> "ExactDigestIndex":
        data = np.load(_npz_path(path), allow_pickle=True)
        idx = cls()
        if "exact_spec" not in data:  # v1: flat bytes + per-entry json refs
            raw = data["digests"].tobytes()
            refs = data["refs"]
            for i in range(len(refs)):
                idx.insert(raw[i * 20:(i + 1) * 20], json.loads(str(refs[i])))
            return idx
        idx._base = _Run(np.ascontiguousarray(data["digests"]).view("S20"),
                         np.asarray(data["carrier_idx"], dtype=np.int32),
                         np.asarray(data["offsets"], dtype=np.int64))
        idx._carriers = [json.loads(str(c)) for c in data["carriers"]]
        idx._carrier_ids = {}
        for i, c in enumerate(idx._carriers):
            try:
                idx._carrier_ids[c] = i
            except TypeError:  # unhashable carrier (e.g. json list)
                pass
        idx._len = len(idx._base)
        return idx


class MinHashLSHIndex:
    """Near-duplicate index: LSH band buckets over MinHash signatures.
    The small-scale host reference of ``near_index.DeviceNearIndex``,
    which is the one the engine serves from: same rows, same answers.

    ``num_perms = bands * rows``.  A query hashes each signature band;
    items sharing any band bucket become candidates, then the true
    signature-agreement score is computed vectorized against the stored
    signature matrix (host numpy) and thresholded.
    """

    def __init__(self, num_perms: int = 64, bands: int = 16) -> None:
        if num_perms % bands:
            raise ValueError(f"bands {bands} must divide num_perms {num_perms}")
        self.num_perms = num_perms
        self.bands = bands
        self.rows = num_perms // bands
        self._buckets: list[dict[bytes, list[int]]] = [{} for _ in range(bands)]
        # Rows accumulate in a list (O(1) amortized add); the dense matrix is
        # materialized lazily and cached for queries.
        self._rows: list[np.ndarray] = []
        self._sigs_cache: np.ndarray | None = None
        self._refs: list[Any] = []
        # ref -> ALL item ids carrying it (hashable refs only): O(1)
        # signature_of (latest id) and O(items-of-ref) remove — a linear
        # _refs scan per delete would make churn quadratic at the scale
        # the exact index is engineered for.
        self._ids_by_ref: dict[Any, list[int]] = {}
        self._dead = 0  # tombstoned rows (compacted when they dominate)

    def __len__(self) -> int:
        return len(self._refs)

    def _band_keys(self, sig: np.ndarray) -> list[bytes]:
        return [sig[b * self.rows:(b + 1) * self.rows].tobytes()
                for b in range(self.bands)]

    def add(self, sig: np.ndarray, ref: Any) -> int:
        """Insert; returns the item id, or -1 for an all-``EMPTY``
        signature (a chunk/file with no sketch survivors carries no
        similarity information — indexing it would make every such item
        a spurious 1.0-score near-dup of every other)."""
        sig = np.asarray(sig, dtype=np.uint32)
        if sig.shape != (self.num_perms,):
            raise ValueError(f"signature shape {sig.shape} != ({self.num_perms},)")
        if (sig == EMPTY).all():
            return -1
        item = len(self._refs)
        self._refs.append(ref)
        self._rows.append(sig)
        self._sigs_cache = None
        try:
            self._ids_by_ref.setdefault(ref, []).append(item)
        except TypeError:
            pass  # unhashable ref: signature_of/remove unsupported for it
        for b, key in enumerate(self._band_keys(sig)):
            self._buckets[b].setdefault(key, []).append(item)
        return item

    def query(self, sig: np.ndarray, top_k: int = 5,
              min_similarity: float = 0.5) -> list[tuple[Any, float]]:
        """Top-k near-dup candidates with signature-agreement scores.

        Scoring is plain numpy: a per-query candidate set is at most a
        few thousand rows, where host vector ops win outright — eager
        accelerator dispatch costs ~ms per op (tens of ms on a remote
        backend), turning a retrieval sweep into dispatch overhead.  The
        mesh-sharded query path uses the :attr:`signatures` matrix with
        its own jitted collectives instead.
        """
        sig = np.asarray(sig, dtype=np.uint32)
        if (sig == EMPTY).all():
            return []
        cand: set[int] = set()
        for b, key in enumerate(self._band_keys(sig)):
            cand.update(self._buckets[b].get(key, ()))
        if not cand:
            return []
        # Live candidates in the order they were added, so that the stable
        # sort leaves ties older row first: the rule the device index
        # (near_index.py) ranks by too.
        ids = np.array(sorted(i for i in cand if self._refs[i] is not None),
                       dtype=np.int64)
        if not len(ids):
            return []
        sigs = self.signatures
        scores = (sigs[ids] == sig[None, :]).mean(axis=1, dtype=np.float32)
        order = np.argsort(-scores, kind="stable")[:top_k]
        return [(self._refs[int(ids[i])], float(scores[i]))
                for i in order if scores[i] >= min_similarity]

    def remove(self, ref: Any) -> int:
        """Tombstone every item carrying ``ref`` (deleted file); queries
        skip tombstones.  When tombstones outnumber live rows the whole
        index compacts (ids, rows, buckets rebuilt) — without this,
        create/delete churn grows signature storage and band buckets
        without bound.  Returns the number of items removed."""
        try:
            ids = self._ids_by_ref.pop(ref, None)
        except TypeError:
            # Unhashable refs never enter the ref map — fall back to the
            # linear scan so they still tombstone.
            ids = [i for i, r in enumerate(self._refs) if r == ref]
            for i in ids:
                self._refs[i] = None
            self._dead += len(ids)
            self._maybe_compact()
            return len(ids)
        if not ids:
            return 0
        for i in ids:
            self._refs[i] = None
        self._dead += len(ids)
        self._maybe_compact()
        return len(ids)

    def _maybe_compact(self) -> None:
        if self._dead <= max(len(self._refs) - self._dead, 1024):
            return
        live = [i for i, r in enumerate(self._refs) if r is not None]
        self._refs = [self._refs[i] for i in live]
        self._rows = [self._rows[i] for i in live]
        self._sigs_cache = None
        self._dead = 0
        self._reindex()

    def _reindex(self) -> None:
        """Rebuild band buckets + the ref map from _refs/_rows (shared by
        snapshot load and tombstone compaction)."""
        self._buckets = [{} for _ in range(self.bands)]
        self._ids_by_ref = {}
        for item, (ref, sig) in enumerate(zip(self._refs, self._rows)):
            for b, key in enumerate(self._band_keys(sig)):
                self._buckets[b].setdefault(key, []).append(item)
            if ref is not None:
                try:
                    self._ids_by_ref.setdefault(ref, []).append(item)
                except TypeError:
                    pass

    def signature_of(self, ref: Any) -> np.ndarray | None:
        """Latest stored signature for ``ref`` (None when unindexed or
        removed) — the entry point for ref-keyed near-dup queries."""
        try:
            ids = self._ids_by_ref.get(ref)
        except TypeError:
            return None
        return self._rows[ids[-1]] if ids else None

    @property
    def signatures(self) -> np.ndarray:
        """The (N, P) stored signature matrix (for sharded/mesh queries)."""
        if self._sigs_cache is None:
            self._sigs_cache = (np.stack(self._rows) if self._rows
                                else np.zeros((0, self.num_perms), np.uint32))
        return self._sigs_cache

    # -- persistence ------------------------------------------------------

    def save(self, path: str) -> None:
        _atomic_savez(
            path, sigs=self.signatures,
            refs=np.array([json.dumps(r) for r in self._refs], dtype=object),
            num_perms=self.num_perms, bands=self.bands,
            sig_spec=SIG_SPEC_VERSION)

    @classmethod
    def load(cls, path: str) -> "MinHashLSHIndex":
        data = np.load(_npz_path(path), allow_pickle=True)
        spec = int(data["sig_spec"]) if "sig_spec" in data else 1
        if spec != SIG_SPEC_VERSION:
            raise ValueError(
                f"near-dup index snapshot {path!r} holds spec-v{spec} "
                f"signatures, this build computes spec-v{SIG_SPEC_VERSION}; "
                "the sets are not comparable — delete the snapshot and "
                "re-ingest (exact dedup state is unaffected)")
        idx = cls(int(data["num_perms"]), int(data["bands"]))
        sigs = np.asarray(data["sigs"], dtype=np.uint32)
        idx._rows = list(sigs)
        idx._sigs_cache = sigs if len(sigs) else None
        idx._refs = [json.loads(str(r)) for r in data["refs"]]
        idx._reindex()
        idx._dead = sum(1 for r in idx._refs if r is None)
        return idx


def _npz_path(path: str) -> str:
    return path if path.endswith(".npz") else path + ".npz"


def _atomic_savez(path: str, compress: bool = True, **arrays) -> None:
    """Write-then-rename snapshot (reference: tracker_save_storages() writes
    its ``.dat`` files the same way for crash consistency).  compress=False
    for columns that will not compress (e.g. SHA1 digests) — at tens of
    millions of entries the zlib pass dominates snapshot time."""
    final = _npz_path(path)
    tmp = final + ".tmp"
    (np.savez_compressed if compress else np.savez)(tmp, **arrays)
    # np.savez appends .npz to paths without it.
    os.replace(tmp + ".npz", final)
