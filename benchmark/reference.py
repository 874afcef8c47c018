"""The plain reference: what the store must hold for a given byte string.

Nothing here imports the program (``fastdfs_tpu``), JAX, or a table the
program made.  The gear table is generated from its definition
(murmur3's 32-bit finalizer of byte+1), the permutation constants from
their seed, SHA-1 is ``hashlib``'s.  Widths come in as arguments from the
configuration file (``widths``), never from the program's defaults.

* :func:`recipe` — ``[(length, sha1)]`` per chunk: gear content-defined
  chunking of each ``dedup_segment_bytes`` segment on its own, hashlib per chunk.
* :func:`file_signature` — the MinHash "survivor sketch" signature of a
  file: the element-wise minimum of its chunks' signatures.
* :func:`cuts_serial` — the per-byte serial chunker, the referee of the
  vectorised one (used by the tests on small inputs).
"""

from __future__ import annotations

import hashlib

import numpy as np

WINDOW = 32                      # a gear hash sees its trailing 32 bytes
_TILE = 1 << 20

SAMPLE_MASK = np.uint32(0xFF)    # a shingle hash survives iff its low byte is 0
NUM_SEGMENTS = 1024
EMPTY = np.uint32(0xFFFFFFFF)
_POLY_B = np.uint32(0x01000193)
_MINHASH_SEED = 0x5F3759DF


def _fmix32(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = x.astype(np.uint32)
        x ^= x >> np.uint32(16)
        x *= np.uint32(0x85EBCA6B)
        x ^= x >> np.uint32(13)
        x *= np.uint32(0xC2B2AE35)
        x ^= x >> np.uint32(16)
    return x


GEAR = _fmix32(np.arange(1, 257, dtype=np.uint32))


# -- content-defined chunking --------------------------------------------------

def _window_hashes(buf: np.ndarray) -> np.ndarray:
    """h[i] = sum_k GEAR[buf[i-k]] << k over the trailing 32 bytes."""
    with np.errstate(over="ignore"):
        h = GEAR[buf]
        w = 1
        while w < WINDOW:
            shifted = np.zeros_like(h)
            shifted[w:] = h[:-w]
            h = h + (shifted << np.uint32(w))
            w <<= 1
    return h


def _candidates(buf: np.ndarray, avg_bits: int) -> np.ndarray:
    mask = np.uint32((1 << avg_bits) - 1)
    out = []
    for t in range(0, len(buf), _TILE):
        lo = max(0, t - (WINDOW - 1))
        h = _window_hashes(buf[lo:t + _TILE])[t - lo:]
        out.append(np.nonzero((h & mask) == 0)[0].astype(np.int64) + t)
    return np.concatenate(out) if out else np.zeros(0, np.int64)


def cuts(data: bytes, min_size: int, avg_bits: int, max_size: int) -> list[int]:
    """Exclusive chunk ends: cut after the first position at least
    ``min_size`` into the chunk whose windowed hash has ``avg_bits`` low
    zero bits, or at ``max_size``."""
    n = len(data)
    if n == 0:
        return []
    cand = _candidates(np.frombuffer(data, dtype=np.uint8), avg_bits)
    out, last = [], 0
    while n - last > max_size or (n - last >= min_size and len(cand)):
        lo = np.searchsorted(cand, last + min_size - 1, side="left")
        hi = np.searchsorted(cand, last + max_size - 1, side="right")
        if lo < hi:
            cut = int(cand[lo]) + 1
        elif n - last > max_size:
            cut = last + max_size
        else:
            break
        out.append(cut)
        last = cut
    if last < n:
        out.append(n)
    return out


def cuts_serial(data: bytes, min_size: int, avg_bits: int,
                max_size: int) -> list[int]:
    """The canonical serial chunker, one byte at a time (hash reset at
    each chunk start).  Far too slow for a run: the tests hold
    :func:`cuts` to it on small inputs."""
    mask = (1 << avg_bits) - 1
    table = [int(x) for x in GEAR]
    out, last, h = [], 0, 0
    for pos, byte in enumerate(data):
        h = ((h << 1) + table[byte]) & 0xFFFFFFFF
        size = pos - last + 1
        if (size >= min_size and (h & mask) == 0) or size >= max_size:
            out.append(pos + 1)
            last, h = pos + 1, 0
    if last < len(data):
        out.append(len(data))
    return out


def segment_cuts(data: bytes, widths: dict) -> list[tuple[int, list[int]]]:
    """[(segment base, chunk ends within the segment)]: the daemon chunks
    each ``dedup_segment_bytes`` segment on its own (a segment end is a
    cut)."""
    seg_bytes = widths["dedup_segment_bytes"]
    return [(base, cuts(data[base:base + seg_bytes], widths["cdc_min_size"],
                        widths["cdc_avg_bits"], widths["cdc_max_size"]))
            for base in range(0, len(data), seg_bytes)]


def recipe(data: bytes, widths: dict, segs=None) -> list[tuple[int, bytes]]:
    """[(length, sha1)] as the daemon must store it."""
    out = []
    for base, ends in segs or segment_cuts(data, widths):
        last = 0
        for cut in ends:
            out.append((cut - last,
                        hashlib.sha1(data[base + last:base + cut]).digest()))
            last = cut
    return out


# -- MinHash survivor sketch -----------------------------------------------------

def _perm_constants(num_perms: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.RandomState(_MINHASH_SEED & 0x7FFFFFFF)
    a = (rng.randint(0, 1 << 31, size=num_perms, dtype=np.uint64) * 2
         + 1).astype(np.uint32)
    b = rng.randint(0, 1 << 32, size=num_perms, dtype=np.uint64).astype(np.uint32)
    return a, b


def _shingle_hashes(buf: np.ndarray, k: int) -> np.ndarray:
    """Polynomial hash of buf[i:i+k] at every i, zeros past the end."""
    d = np.concatenate([buf, np.zeros(k, np.uint8)]).astype(np.uint32)
    n = len(buf)
    h = np.zeros(n, np.uint32)
    with np.errstate(over="ignore"):
        for j in range(k):
            h = h * _POLY_B + d[j:j + n]
    return h


def _survivors(seg: np.ndarray, ends: np.ndarray, k: int) -> np.ndarray:
    """The thinned survivor hashes of every chunk of one segment, pooled:
    per chunk, per (word index mod NUM_SEGMENTS), the least shingle hash
    with a zero low byte among the chunk's complete shingles."""
    starts = np.concatenate([[0], ends[:-1]])
    lens = ends - starts
    pos, hv = [], []
    for t in range(0, len(seg), _TILE):      # tiled: 4 B of hash per byte
        h = _shingle_hashes(seg[t:t + _TILE + k - 1], k)[:_TILE]
        p = np.nonzero((h & SAMPLE_MASK) == 0)[0]
        pos.append(p + t)
        hv.append(h[p])
    pos, hv = np.concatenate(pos), np.concatenate(hv)
    chunk = np.searchsorted(ends, pos, side="right")
    rel = pos - starts[chunk]
    ok = (lens[chunk] >= k) & (rel <= lens[chunk] - k)
    chunk, rel = chunk[ok], rel[ok]
    vals = [hv[ok]]
    keys = [chunk * NUM_SEGMENTS + (rel // 4) % NUM_SEGMENTS]
    # A chunk shorter than a shingle hashes its zero-padded windows.
    for c in np.nonzero(lens < k)[0]:
        piece = seg[starts[c]:ends[c]]
        hh = _shingle_hashes(piece, k)[:max(len(piece), 1)]
        p = np.nonzero((hh & SAMPLE_MASK) == 0)[0]
        vals.append(hh[p])
        keys.append(c * NUM_SEGMENTS + (p // 4) % NUM_SEGMENTS)
    vals, keys = np.concatenate(vals), np.concatenate(keys)
    order = np.lexsort((vals, keys))
    vals, keys = vals[order], keys[order]
    first = np.ones(len(keys), bool)
    first[1:] = keys[1:] != keys[:-1]
    return vals[first]


def file_signature(data: bytes, widths: dict, segs=None) -> np.ndarray:
    """(num_perms,) uint32: min over the file's chunks of each chunk's
    MinHash signature, i.e. min over all thinned survivors of a*x+b."""
    k, perms = widths["shingle"], widths["num_perms"]
    a, b = _perm_constants(perms)
    sig = np.full(perms, EMPTY, np.uint32)
    buf = np.frombuffer(data, np.uint8)
    for base, ends in segs or segment_cuts(data, widths):
        z = _survivors(buf[base:base + ends[-1]], np.asarray(ends, np.int64), k)
        z = z[z != EMPTY]
        with np.errstate(over="ignore"):
            for lo in range(0, len(z), 1 << 16):
                block = z[lo:lo + (1 << 16)]
                perm = block[None, :] * a[:, None] + b[:, None]
                sig = np.minimum(sig, perm.min(axis=1))
    return sig
