"""Content-defined chunking via a gear rolling hash, position-parallel.

Replaces the sequential chunk loop of the reference upload path
(``storage/storage_dio.c:dio_write_file()`` — ``buff_size`` chunks with a
CRC32 carried across iterations) with TPU-parallel chunking.

The serial gear hash is ``h = (h << 1) + gear[b[i]]`` with a cut candidate
wherever ``h & mask == 0``.  Because ``<< 1`` pushes a byte's contribution
out of a 32-bit register after 32 steps, ``h`` at position ``i`` depends
only on the trailing 32-byte window:

    h[i] = sum_{k=0..31} gear[b[i-k]] << k        (mod 2^32)

which is computable *independently per position* — 32 shifted adds over the
whole buffer, fully vectorized on TPU lanes.  No seam reconciliation is
needed for the hash itself; the only sequential part is greedy cut
*selection* under min/max chunk-size constraints, which runs over the
sparse candidate list on the host.

Cut-point equality with the canonical serial algorithm (which resets the
hash at each chunk start) holds whenever ``min_size >= 32``: every position
eligible for a cut is at least ``min_size`` bytes past the previous cut, so
the 32-byte window never straddles a chunk boundary.  This is the
"blockwise CDC with seam fixup" design from SURVEY.md §5, validated
property-based in ``tests/test_gear_cdc.py`` / ``tests/test_cdc_kernels.py``.

Two throughput refinements from the vector-chunking literature (round 13):

- **Lane-parallel hashing** (arXiv:2505.21194): the jax path folds the
  byte stream into a ``(LANES, cols)`` grid with a 31-byte halo carried
  from the previous row, so the windowed sum vectorizes across both the
  TPU sublane and lane axes instead of one long roll chain.  Bit-identical
  to the 1-D formulation (the halo makes every kept window complete).
- **Skip-min evaluation** (arXiv:2508.05797): hash evaluation *skips* the
  ``min_size`` bytes after every accepted cut instead of rolling through
  them, restarting the hash at the first eligible position.  This moves
  boundaries relative to the default policy — cuts are content addresses —
  so it ships strictly as opt-in ``cdc_policy=CDC_POLICY_SKIPMIN`` with
  its own serial referee (``chunk_stream_skipmin_ref``), never as a
  default.  See OPERATIONS.md "Ingest kernels & chunking policies".
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np


def _fmix32(x: np.ndarray) -> np.ndarray:
    """murmur3 finalizer: the gear table's generator."""
    x = np.asarray(x, dtype=np.uint32).copy()
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


# Chunker spec version: bumped whenever cut-point behavior changes (the
# table, window, or selection rule).  v2 = the fmix32 table (round 5).
# Dedup state built under another spec chunks the same content at
# different offsets, so exact-dedup hits would silently drop to ~0; the
# sidecar discards stale-spec state at load (reads/recipes are
# unaffected — chunk stores are content-addressed).
CDC_SPEC_VERSION = 2

# Cut-selection policies.  Policy is orthogonal to the spec version: the
# DEFAULT policy under spec v2 is frozen (golden-pinned), and SKIPMIN is
# a distinct, explicitly-chosen policy with different boundaries — state
# built under one policy must never be queried under the other (the
# sidecar discards snapshots on policy mismatch, like spec mismatch).
CDC_POLICY_DEFAULT = 1   # serial-equivalent rolling evaluation (frozen)
CDC_POLICY_SKIPMIN = 2   # skip min_size bytes after each cut (arXiv:2508.05797)

# Deterministic 256-entry gear table, defined as fmix32(byte+1) so it is
# COMPUTABLE, not just storable: a 256-entry gather lowers to a scalar
# loop on TPU, while the same lookup as inline fmix32 arithmetic runs at
# vector speed (neither rate is measured on this machine yet: PERF.md).  The C++
# chunker and the CPU reference paths keep using the materialized table
# (native/gen_gear.py regenerates gear_gen.h from this array), so every
# node still chunks identically.
GEAR_TABLE = _fmix32(np.arange(1, 257, dtype=np.uint32))

WINDOW = 32
_HALO = WINDOW - 1

# Lane-parallel fold geometry: 256 rows keeps the row length >= the halo
# for every pow2 buffer >= 8 KiB while giving XLA a (256, cols) grid that
# tiles the 8x128 VPU cleanly (sublane axis full, lane axis contiguous).
_LANES = 256
_LANE_MIN_BYTES = _LANES * WINDOW  # smallest fold where cols >= WINDOW > halo

# Reusable host staging buffers for device_put: a FRESH host allocation
# pays page faults and per-buffer setup on every transfer, a reused one
# does not (what that is worth on the v5e's PCIe link is not measured
# yet: PERF.md).
# Thread-local: concurrent fingerprint calls must not share staging.
# (device_put snapshots the buffer synchronously, so reuse right after
# dispatch is safe.)
_staging = threading.local()


def staging_buffer(size: int, slot: int = 0) -> np.ndarray:
    """Reusable host staging buffer, keyed by (size, slot).

    ``slot`` lets callers double-buffer: PJRT host-buffer donation
    semantics are backend-dependent (some clients hold the host buffer
    zero-copy until the transfer completes), so a caller that dispatches
    tile N+1 before fetching tile N must rotate >= 2 slots per size or
    risk overwriting bytes still in flight (dedup/engine.py
    fingerprint()).
    """
    bufs = getattr(_staging, "bufs", None)
    if bufs is None:
        bufs = _staging.bufs = {}
    key = (size, slot)
    buf = bufs.get(key)
    if buf is None:
        buf = bufs[key] = np.zeros(size, dtype=np.uint8)
    return buf


def staging_buffer_stats() -> dict:
    """Introspection for the growth audit: count + total bytes of live
    staging buffers on THIS thread (tests assert reuse, not realloc)."""
    bufs = getattr(_staging, "bufs", None) or {}
    return {
        "buffers": len(bufs),
        "bytes": int(sum(b.nbytes for b in bufs.values())),
        "keys": sorted(bufs.keys()),
    }

# Default chunking geometry (bytes).  avg 8 KiB => 13 mask bits.
DEFAULT_MIN_SIZE = 2048
DEFAULT_AVG_BITS = 13
DEFAULT_MAX_SIZE = 65536


def gear_hashes_ref(data: bytes | np.ndarray) -> np.ndarray:
    """Serial CPU reference: windowed gear hash at every position."""
    buf = np.frombuffer(bytes(data), dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else np.asarray(data, dtype=np.uint8)
    out = np.zeros(len(buf), dtype=np.uint32)
    h = np.uint32(0)
    with np.errstate(over="ignore"):
        for i, b in enumerate(buf):
            h = np.uint32(h << np.uint32(1)) + GEAR_TABLE[b]
            out[i] = h
    return out


def _inline_gear(data: jax.Array) -> jax.Array:
    """Gear table values as inline fmix32 arithmetic (no gather)."""
    x = data.astype(jnp.uint32) + jnp.uint32(1)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))


def _windowed_sum_1d(g: jax.Array) -> jax.Array:
    # Prefix doubling: S_w[i] = sum_{k<w} g[i-k] << k satisfies
    # S_2w[i] = S_w[i] + (S_w[i-w] << w), so the 32-term window needs
    # log2(32) = 5 shifted adds, not 31.
    h = g
    w = 1
    while w < WINDOW:
        shifted = jnp.roll(h, w).at[:w].set(0)  # S_w[i-w], zero for i<w
        h = h + (shifted << np.uint32(w))
        w <<= 1
    return h


def _windowed_sum_rows(g_ext: jax.Array) -> jax.Array:
    """Row-wise prefix-doubling windowed sum over ``(rows, cols)``."""
    h = g_ext
    w = 1
    while w < WINDOW:
        shifted = jnp.pad(h, ((0, 0), (w, 0)))[:, :-w]
        h = h + (shifted << np.uint32(w))
        w <<= 1
    return h


@functools.partial(jax.jit, static_argnames=())
def gear_hashes(data: jax.Array) -> jax.Array:
    """Position-parallel gear hashes: ``h[i]`` for every byte position.

    ``data`` is uint8 of shape ``(n,)``; returns uint32 ``(n,)`` equal to the
    serial rolling value at each position (exactly, for all positions).

    The table lookup is computed as inline fmix32 arithmetic (see
    ``GEAR_TABLE``) — pure vector ops, no gather.  Buffers large enough to
    fold are hashed lane-parallel: the stream reshapes to ``(_LANES,
    cols)`` and each row carries a 31-value halo from its predecessor, so
    every kept window is complete and the result is bit-identical to the
    1-D chain while the shifted adds vectorize across both grid axes
    (arXiv:2505.21194's row-folded formulation).
    """
    g = _inline_gear(data)
    n = data.shape[0]
    if n >= _LANE_MIN_BYTES and n % _LANES == 0:
        cols = n // _LANES
        g2 = g.reshape(_LANES, cols)
        # Row r's halo = the 31 trailing values of row r-1 (zeros for r=0):
        # exactly the bytes a 32-wide window at the row head reaches back to.
        halo = jnp.pad(g2[:-1, -_HALO:], ((1, 0), (0, 0)))
        g_ext = jnp.concatenate([halo, g2], axis=1)
        h = _windowed_sum_rows(g_ext)[:, _HALO:]
        return h.reshape(n)
    return _windowed_sum_1d(g)


def candidate_mask(hashes: jax.Array, avg_bits: int = DEFAULT_AVG_BITS) -> jax.Array:
    """Boolean cut-candidate mask: positions where the low ``avg_bits`` of
    the gear hash are zero (expected chunk size ``2**avg_bits``)."""
    mask = np.uint32((1 << avg_bits) - 1)
    return (hashes & mask) == 0


@functools.partial(jax.jit, static_argnames=("avg_bits", "k"))
def gear_candidates(data: jax.Array, n: jax.Array, avg_bits: int,
                    k: int) -> jax.Array:
    """Candidate positions, computed AND compacted on device.

    Returns the first ``k`` candidate positions within the first ``n``
    bytes (sorted, padded with ``len(data)``) as ONE array — every
    fetched array pays a fixed device-to-host latency, and
    the full per-position hash array (4 B/input byte) would cost more to
    fetch than the hashing itself.  The dense mask is never needed: cut
    selection only consumes the sparse candidates.  A full last slot
    signals possible overflow (caller falls back to the dense path).
    """
    h = gear_hashes(data)
    m = candidate_mask(h, avg_bits) & (jnp.arange(data.shape[0]) < n)
    return jnp.nonzero(m, size=k, fill_value=data.shape[0])[0]


def select_cuts(
    candidates: np.ndarray,
    n: int,
    min_size: int = DEFAULT_MIN_SIZE,
    max_size: int = DEFAULT_MAX_SIZE,
) -> list[int]:
    """Greedy cut selection under min/max chunk-size constraints.

    ``candidates`` are sorted candidate positions (cut *after* byte ``i``,
    i.e. chunk end ``i + 1``).  Returns exclusive end offsets of every chunk
    (final offset is ``n``).  Sequential but sparse — O(#cuts log #cands) on
    the host.
    """
    if min_size < WINDOW:
        raise ValueError(f"min_size must be >= {WINDOW} for cut-point "
                         f"equality with the serial reference")
    cuts: list[int] = []
    cand = np.asarray(candidates, dtype=np.int64)
    last = 0
    while n - last > max_size or (n - last >= min_size and len(cand)):
        lo = np.searchsorted(cand, last + min_size - 1, side="left")
        hi = np.searchsorted(cand, last + max_size - 1, side="right")
        if lo < hi:
            cut = int(cand[lo]) + 1
        elif n - last > max_size:
            cut = last + max_size
        else:
            break
        cuts.append(cut)
        last = cut
    if last < n:
        cuts.append(n)
    return cuts


def select_cuts_skipmin(
    data: bytes | np.ndarray,
    candidates: np.ndarray,
    n: int,
    min_size: int = DEFAULT_MIN_SIZE,
    avg_bits: int = DEFAULT_AVG_BITS,
    max_size: int = DEFAULT_MAX_SIZE,
) -> list[int]:
    """Skip-min cut selection from precomputed *windowed* candidates.

    Skip-min restarts the hash at the first eligible position after each
    cut (``last + min_size - 1``), so the hash at position ``p`` covers
    ``[start, p]`` clamped to the 32-byte window.  For
    ``p >= start + WINDOW - 1`` the window is full and the restart hash
    EQUALS the continuous windowed hash — the global candidate list
    applies verbatim.  Only the ``<= 31`` warm-up positions per chunk
    (partial windows) need fresh hashing, done vectorized on the slice.

    Needs the byte buffer (for warm-up hashing) in addition to the
    candidate list.  ``candidates`` must cover ``[0, n)`` densely (every
    windowed-hash candidate), as produced by ``gear_candidates`` /
    ``gear_candidates_np``.
    """
    if min_size < 1:
        raise ValueError("min_size must be >= 1")
    if max_size < min_size:
        raise ValueError("max_size must be >= min_size")
    buf = (np.frombuffer(bytes(data), dtype=np.uint8)
           if isinstance(data, (bytes, bytearray, memoryview))
           else np.asarray(data, dtype=np.uint8))
    mask = np.uint32((1 << avg_bits) - 1)
    cand = np.asarray(candidates, dtype=np.int64)
    cuts: list[int] = []
    last = 0
    while n - last > 0:
        if n - last < min_size:
            cuts.append(n)
            break
        start = last + min_size - 1       # first position a cut may land on
        forced = last + max_size - 1      # reaching this position always cuts
        cutpos = -1
        # Warm-up region: partial-window restart hashes, <= 31 positions.
        warm_end = min(start + WINDOW - 2, forced, n - 1)
        if warm_end >= start:
            wh = gear_hashes_np(buf[start:warm_end + 1])
            hits = np.nonzero((wh & mask) == 0)[0]
            if len(hits):
                cutpos = start + int(hits[0])
        if cutpos < 0:
            # Full-window region: reuse the global windowed candidates.
            lo = np.searchsorted(cand, start + WINDOW - 1, side="left")
            hi = np.searchsorted(cand, min(forced, n - 1), side="right")
            if lo < hi:
                cutpos = int(cand[lo])
        if cutpos >= 0:
            cuts.append(cutpos + 1)
            last = cutpos + 1
        elif n - last >= max_size:
            cuts.append(last + max_size)
            last = last + max_size
        else:
            cuts.append(n)
            break
    return cuts


def chunk_stream(
    data: bytes,
    min_size: int = DEFAULT_MIN_SIZE,
    avg_bits: int = DEFAULT_AVG_BITS,
    max_size: int = DEFAULT_MAX_SIZE,
    cdc_policy: int = CDC_POLICY_DEFAULT,
    _k_override: int | None = None,
) -> list[int]:
    """TPU-parallel CDC: returns exclusive chunk end offsets for ``data``.

    The buffer is zero-padded to the next power of two before the jitted
    hash pass: XLA compiles once per pow2 shape instead of once per file
    size, and trailing padding cannot affect ``h[i]`` for real positions
    (each depends only on the 32 bytes ending at ``i``).

    Only the sparse candidate list leaves the device (expected density
    ``2**-avg_bits``, fetched with 4x headroom); if a pathological input
    exceeds the headroom, the dense mask path recovers exactly.
    ``_k_override`` exists so tests can force that fallback.

    ``cdc_policy`` selects the boundary rule: ``CDC_POLICY_DEFAULT`` is
    cut-identical to ``chunk_stream_ref`` (the frozen content-address
    contract); ``CDC_POLICY_SKIPMIN`` is the opt-in skip-min rule checked
    against ``chunk_stream_skipmin_ref``.  Both share one hash pass.
    """
    if cdc_policy not in (CDC_POLICY_DEFAULT, CDC_POLICY_SKIPMIN):
        raise ValueError(f"unknown cdc_policy {cdc_policy}")
    if not data:
        return []
    n = len(data)
    padded = 1 << max(12, (n - 1).bit_length())  # >= 4 KiB, pow2
    buf = staging_buffer(padded)
    buf[:n] = np.frombuffer(data, dtype=np.uint8)
    buf[n:] = 0
    k = _k_override if _k_override is not None else max(
        padded >> max(avg_bits - 2, 0), 256)
    # device_put (NOT jnp.asarray, which re-wraps the buffer and misses
    # the reused-staging fast path) + ONE fetched array.
    dev = jax.device_put(buf)
    idx = np.asarray(jax.device_get(
        gear_candidates(dev, np.int32(n), avg_bits, k)))
    if idx[-1] >= padded:  # last slot unused => no overflow
        cand = idx[idx < padded].astype(np.int64)
    else:
        # Candidate buffer possibly overflowed (>4x the expected
        # density): fetch the dense mask once (exact, just slower)
        # rather than risk missed cut points.
        hashes = np.asarray(gear_hashes(dev))[:n]
        cand = np.flatnonzero(np.asarray(candidate_mask(hashes, avg_bits)))
    if cdc_policy == CDC_POLICY_SKIPMIN:
        return select_cuts_skipmin(buf[:n], cand, n, min_size, avg_bits,
                                   max_size)
    return select_cuts(cand, n, min_size, max_size)


def gear_hashes_np(data: bytes | np.ndarray) -> np.ndarray:
    """Vectorized NumPy twin of :func:`gear_hashes` (same prefix-doubling
    windowed sum, uint32 wraparound) — for hosts without an accelerator:
    the client-side fingerprint path must not pay a per-byte Python loop
    (``gear_hashes_ref``) or drag JAX into thin client processes."""
    buf = (np.frombuffer(bytes(data), dtype=np.uint8)
           if isinstance(data, (bytes, bytearray, memoryview))
           else np.asarray(data, dtype=np.uint8))
    with np.errstate(over="ignore"):
        x = buf.astype(np.uint32) + np.uint32(1)
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x85EBCA6B)
        x ^= x >> np.uint32(13)
        x *= np.uint32(0xC2B2AE35)
        h = x ^ (x >> np.uint32(16))
        w = 1
        while w < WINDOW:
            shifted = np.zeros_like(h)
            shifted[w:] = h[:-w]
            h = h + (shifted << np.uint32(w))
            w <<= 1
    return h


# Host-path scan tile: large enough to amortize the 5 shifted-add passes,
# small enough that the working set (2 uint32 work buffers per byte) stays
# near L2 instead of streaming 4 B/byte of hashes through main memory.
_NP_TILE = 1 << 20

# Staging slots for the tiled host scan's two uint32 work buffers (hash
# accumulator + shift temporary).  Slots 0/1 are the engine's
# double-buffered device staging; keep these disjoint so a client that
# chunks AND fingerprints on one thread never aliases them.
_NP_WORK_SLOTS = (16, 17)


def _gear_hashes_np_into(buf_slice: np.ndarray, work_h: np.ndarray,
                         work_t: np.ndarray) -> np.ndarray:
    """``gear_hashes_np`` computed in-place inside caller-owned uint32
    work buffers (no per-call temporaries) — the tiled scan's inner loop.
    Returns a view of ``work_h``."""
    m = len(buf_slice)
    h = work_h[:m]
    tmp = work_t[:m]
    with np.errstate(over="ignore"):
        np.copyto(h, buf_slice)          # uint8 widens into the uint32 buffer
        h += np.uint32(1)
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
        w = 1
        while w < WINDOW:
            tmp[w:] = h[:-w]
            tmp[:w] = 0
            tmp <<= np.uint32(w)
            h += tmp
            w <<= 1
    return h


def gear_candidates_np(data: bytes | np.ndarray,
                       avg_bits: int = DEFAULT_AVG_BITS) -> np.ndarray:
    """Windowed-hash candidate positions, scanned in cache-sized tiles.

    Equal to ``np.nonzero(candidate_mask(gear_hashes_np(data)))[0]`` but
    never materializes the full 4-bytes-per-input-byte hash array: each
    1 MiB tile is hashed with a 31-byte halo from its predecessor (so
    every emitted position sees a full window) and only the sparse
    candidate indices survive.  This is the host-path analogue of the
    lane fold — same math, tiled for cache instead of lanes.  The two
    uint32 work buffers come from the thread-local staging pool, so
    repeated calls at any input size reuse ONE fixed allocation
    (asserted by tests/test_cdc_kernels.py's growth audit).
    """
    buf = (np.frombuffer(bytes(data), dtype=np.uint8)
           if isinstance(data, (bytes, bytearray, memoryview))
           else np.asarray(data, dtype=np.uint8))
    n = len(buf)
    mask = np.uint32((1 << avg_bits) - 1)
    if n <= 4096:
        # Tiny inputs: a per-call temporary beats pinning the ~8 MB
        # work pair for a client that only ever chunks small buffers.
        h = gear_hashes_np(buf)
        return np.nonzero((h & mask) == 0)[0]
    span = min(n, _NP_TILE + _HALO)
    work_h = staging_buffer(4 * (_NP_TILE + _HALO),
                            slot=_NP_WORK_SLOTS[0]).view(np.uint32)[:span]
    work_t = staging_buffer(4 * (_NP_TILE + _HALO),
                            slot=_NP_WORK_SLOTS[1]).view(np.uint32)[:span]
    out: list[np.ndarray] = []
    for t in range(0, n, _NP_TILE):
        lo = max(0, t - _HALO)
        h = _gear_hashes_np_into(buf[lo:t + _NP_TILE], work_h, work_t)
        seg = h[t - lo:]
        idx = np.nonzero((seg & mask) == 0)[0]
        if len(idx):
            out.append(idx.astype(np.int64) + t)
    if not out:
        return np.zeros(0, dtype=np.int64)
    return np.concatenate(out)


def chunk_stream_np(
    data: bytes,
    min_size: int = DEFAULT_MIN_SIZE,
    avg_bits: int = DEFAULT_AVG_BITS,
    max_size: int = DEFAULT_MAX_SIZE,
    cdc_policy: int = CDC_POLICY_DEFAULT,
) -> list[int]:
    """CPU-vectorized CDC with the exact cut points of ``chunk_stream`` /
    ``chunk_stream_ref`` (same table, window, and selection rule), or of
    ``chunk_stream_skipmin_ref`` under ``cdc_policy=CDC_POLICY_SKIPMIN``."""
    if cdc_policy not in (CDC_POLICY_DEFAULT, CDC_POLICY_SKIPMIN):
        raise ValueError(f"unknown cdc_policy {cdc_policy}")
    n = len(data)
    if n == 0:
        return []
    candidates = gear_candidates_np(data, avg_bits)
    if cdc_policy == CDC_POLICY_SKIPMIN:
        return select_cuts_skipmin(data, candidates, n, min_size, avg_bits,
                                   max_size)
    return select_cuts(candidates, n, min_size, max_size)


def chunk_stream_ref(
    data: bytes,
    min_size: int = DEFAULT_MIN_SIZE,
    avg_bits: int = DEFAULT_AVG_BITS,
    max_size: int = DEFAULT_MAX_SIZE,
) -> list[int]:
    """Canonical serial CDC (hash reset at each chunk start) — the CPU
    referee for cut-point equality tests."""
    if min_size < WINDOW:
        raise ValueError(f"min_size must be >= {WINDOW}")
    mask = np.uint32((1 << avg_bits) - 1)
    table = GEAR_TABLE
    buf = np.frombuffer(data, dtype=np.uint8)
    n = len(buf)
    cuts: list[int] = []
    last = 0
    h = np.uint32(0)
    pos = 0
    with np.errstate(over="ignore"):
        while pos < n:
            h = np.uint32(h << np.uint32(1)) + table[buf[pos]]
            size = pos - last + 1
            if (size >= min_size and (h & mask) == 0) or size >= max_size:
                cuts.append(pos + 1)
                last = pos + 1
                h = np.uint32(0)
            pos += 1
    if last < n:
        cuts.append(n)
    return cuts


def chunk_stream_skipmin_ref(
    data: bytes,
    min_size: int = DEFAULT_MIN_SIZE,
    avg_bits: int = DEFAULT_AVG_BITS,
    max_size: int = DEFAULT_MAX_SIZE,
) -> list[int]:
    """Serial referee for the skip-min policy (``cdc_policy=2``).

    After each accepted cut the scanner JUMPS ``min_size - 1`` bytes and
    restarts the hash at the first eligible position — the skipped bytes
    are never hashed (that is the throughput win: ~``min/avg`` of the
    stream is skipped).  A cut lands at the first restart-hash candidate,
    or is forced at ``max_size``.  Boundaries differ from the default
    policy, so this is a distinct content-address namespace.
    """
    if min_size < 1:
        raise ValueError("min_size must be >= 1")
    if max_size < min_size:
        raise ValueError("max_size must be >= min_size")
    mask = np.uint32((1 << avg_bits) - 1)
    table = GEAR_TABLE
    buf = np.frombuffer(data, dtype=np.uint8)
    n = len(buf)
    cuts: list[int] = []
    last = 0
    with np.errstate(over="ignore"):
        while n - last > 0:
            if n - last < min_size:
                cuts.append(n)
                break
            h = np.uint32(0)
            cut = -1
            end = min(last + max_size - 1, n - 1)
            for pos in range(last + min_size - 1, end + 1):
                h = np.uint32(h << np.uint32(1)) + table[buf[pos]]
                if (h & mask) == 0:
                    cut = pos + 1
                    break
            if cut < 0:
                cut = last + max_size if n - last >= max_size else n
            cuts.append(cut)
            if cut >= n:
                break
            last = cut
    return cuts
