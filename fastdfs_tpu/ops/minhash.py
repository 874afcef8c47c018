"""MinHash near-duplicate fingerprints on TPU (v2 "survivor sketch" spec).

The tracker-side near-dup index (north star: "tracker's file-id index
backed by a jax.numpy cosine/MinHash similarity search") needs a compact
per-chunk signature whose agreement rate estimates Jaccard similarity of
the underlying shingle sets.  The v1 spec permuted EVERY shingle hash
through all ``P`` universal hashes — ``P`` multiply-add-min triples per
byte, ~192 vector ops/byte, which made MinHash the pipeline's
costliest stage.  The v2 spec is a
TPU-first two-stage sketch with identical set semantics:

1. **Shingle hashes** — polynomial hash of every ``k``-byte window
   (unchanged from v1);
2. **Survivor sampling** — keep only hashes with ``h & SAMPLE_MASK == 0``
   (rate 1/256).  Sampling is keyed on the VALUE, so it is invariant to
   where content sits in the stream: two near-duplicate chunks sample
   (almost exactly) the same elements.  Jaccard of the sampled sets is an
   unbiased estimate of Jaccard of the full sets;
3. **Segment-min compaction** — the sparse survivors are compacted to a
   dense ``NUM_SEGMENTS``-wide vector ``z`` by taking the min surviving
   hash per segment (``segment = word_index mod NUM_SEGMENTS``; empty
   segments hold ``EMPTY``).  When two survivors share a segment the
   larger is dropped (~1-11% of survivors depending on chunk size) —
   a small position-dependent thinning that both the CPU reference and
   the TPU kernel apply identically;
4. **Permutation MinHash over survivors** — ``P`` universal-hash
   permutations ``h_j(x) = a_j * x + b_j`` min-reduced over the ~256
   survivors instead of all ~65k positions.  Signature agreement
   fraction ≈ Jaccard of the survivor (≈ shingle) sets.

Why this is the TPU shape of the problem: stage 2+3 are one cheap pass
(compare + select + min) that shrinks the element count 64-256x, so the
expensive ``P``-way permutation work runs on 1/64th of the data and the
whole sketch drops from ~192 to ~25 vector ops per ingested byte.

A chunk with no survivors signs as all-``EMPTY``; ``EMPTY`` is neutral
in element-wise mins, so file-level signatures (min over chunk
signatures) remain "MinHash of the union of the chunks' survivor sets".

No reference equivalent — upstream FastDFS has only exact CRC32
(SURVEY.md §0 north-star note).  Bit-exactness of the Pallas twin
(``ops/pallas_minhash.py``) against this reference is enforced by
``tests/test_pallas_kernels.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_SHINGLE = 5
DEFAULT_PERMS = 64

SAMPLE_MASK = np.uint32(0xFF)   # keep h iff (h & SAMPLE_MASK) == 0: rate 1/256
NUM_SEGMENTS = 1024             # z width; segment = word_index % NUM_SEGMENTS
EMPTY = np.uint32(0xFFFFFFFF)   # empty-segment sentinel, neutral under min

_MINHASH_SEED = 0x5F3759DF
_POLY_B = np.uint32(0x01000193)  # FNV-32 prime as shingle-hash base


def _perm_constants(num_perms: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.RandomState(_MINHASH_SEED & 0x7FFFFFFF)
    a = (rng.randint(0, 1 << 31, size=num_perms, dtype=np.uint64) * 2 + 1).astype(np.uint32)
    b = rng.randint(0, 1 << 32, size=num_perms, dtype=np.uint64).astype(np.uint32)
    return a, b


@functools.partial(jax.jit, static_argnames=("k",))
def shingle_hashes(data: jax.Array, k: int = DEFAULT_SHINGLE) -> jax.Array:
    """Polynomial hashes of all ``k``-byte shingles of uint8 ``(n,)`` data.

    Returns uint32 ``(n,)``; entry ``i`` hashes ``data[i : i+k]`` and the
    trailing ``k-1`` entries (incomplete windows) are masked to the hash of
    the shorter suffix — callers slice ``[: n-k+1]`` for exact semantics.
    """
    d = data.astype(jnp.uint32)
    h = jnp.zeros_like(d)
    for j in range(k):
        shifted = jnp.roll(d, -j).at[-j:].set(0) if j else d
        h = h * _POLY_B + shifted
    return h


def _valid_mask(n: int, lengths: jax.Array, k: int) -> jax.Array:
    """(N, n) bool: complete-shingle positions (degenerate chunks shorter
    than ``k`` hash their zero-padded window at positions < max(len, 1))."""
    pos = jnp.arange(n, dtype=jnp.int32)[None, :]
    lens = lengths.astype(jnp.int32)[:, None]
    valid = pos <= (lens - k)
    return jnp.where(lens >= k, valid, pos < jnp.maximum(lens, 1))


@functools.partial(jax.jit, static_argnames=("k",))
def survivor_segmin(data: jax.Array, lengths: jax.Array,
                    k: int = DEFAULT_SHINGLE) -> jax.Array:
    """Stages 1-3 of the sketch: uint8 ``(N, L)`` + lengths ``(N,)`` →
    uint32 ``(N, NUM_SEGMENTS)`` survivor vector ``z``.

    ``z[s]`` is the smallest surviving shingle hash whose byte position
    ``p`` satisfies ``(p // 4) % NUM_SEGMENTS == s`` (word-granular
    striding, so ``z`` is independent of the padded container length),
    or ``EMPTY`` when no survivor maps to ``s``.
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    lengths = jnp.asarray(lengths, dtype=jnp.int32)
    n, L = data.shape
    block = 4 * NUM_SEGMENTS
    pad = (-L) % block
    h = jax.vmap(lambda row: shingle_hashes(row, k))(
        jnp.pad(data, ((0, 0), (0, pad))))
    surv = _valid_mask(L + pad, lengths, k) & ((h & SAMPLE_MASK) == 0)
    hm = jnp.where(surv, h, EMPTY)
    # position p = block*b + 4*s + r  →  word p//4 = NUM_SEGMENTS*b + s,
    # so a plain reshape groups positions by segment.
    return hm.reshape(n, (L + pad) // block, NUM_SEGMENTS, 4).min(axis=(1, 3))


_MIN_BLOCK = 512  # positions per scan step: keeps the (P, block)
                  # permuted-hash tile resident instead of an O(P*L) array


@functools.partial(jax.jit, static_argnames=("num_perms",))
def minhash_signature(hashes: jax.Array, num_perms: int = DEFAULT_PERMS,
                      valid: jax.Array | None = None) -> jax.Array:
    """MinHash signature of a set of element hashes (stage 4).

    ``hashes``: uint32 ``(m,)``.  ``valid``: optional bool ``(m,)`` mask
    (excluded positions contribute nothing).  Returns uint32
    ``(num_perms,)``; all-invalid input signs as all-``EMPTY``.

    Computed as a running min over position blocks (lax.scan): the
    naive ``(P, m)`` permuted matrix is never materialized, so memory is
    O(P * block) regardless of input length.
    """
    a, b = _perm_constants(num_perms)
    av = jnp.asarray(a)[:, None]
    bv = jnp.asarray(b)[:, None]
    m = hashes.shape[0]
    pad = (-m) % _MIN_BLOCK
    h = jnp.pad(hashes, (0, pad))
    v = (jnp.pad(valid, (0, pad)) if valid is not None
         else jnp.pad(jnp.ones((m,), dtype=bool), (0, pad)))
    h_blocks = h.reshape(-1, _MIN_BLOCK)
    v_blocks = v.reshape(-1, _MIN_BLOCK)

    def body(carry, hv_block):
        hb, vb = hv_block
        perm = hb[None, :] * av + bv                      # (P, block)
        perm = jnp.where(vb[None, :], perm, EMPTY)
        return jnp.minimum(carry, perm.min(axis=1)), None

    init = jnp.full((num_perms,), EMPTY, dtype=jnp.uint32)
    sig, _ = jax.lax.scan(body, init, (h_blocks, v_blocks))
    return sig


@functools.partial(jax.jit, static_argnames=("num_perms", "k"))
def minhash_batch(data: jax.Array, lengths: jax.Array,
                  num_perms: int = DEFAULT_PERMS,
                  k: int = DEFAULT_SHINGLE) -> jax.Array:
    """Signatures for a batch of chunks: uint8 ``(N, L)`` + lengths ``(N,)``
    → uint32 ``(N, num_perms)``.

    CONTRACT: rows must be zero past their length (shared with
    ``sha1_batch``); the survivor stage hashes padded windows and relies
    on the validity mask to exclude them.
    """
    z = survivor_segmin(data, lengths, k)
    return jax.vmap(
        lambda zr: minhash_signature(zr, num_perms, zr != EMPTY))(z)


def estimate_jaccard(sig_a: jax.Array, sig_b: jax.Array) -> jax.Array:
    """Agreement fraction of two signatures ≈ Jaccard similarity.

    Broadcasts: ``(…, P)`` vs ``(…, P)`` → ``(…,)`` float32.
    """
    return (sig_a == sig_b).mean(axis=-1).astype(jnp.float32)
