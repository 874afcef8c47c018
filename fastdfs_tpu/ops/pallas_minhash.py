"""Pallas TPU kernel for the MinHash survivor sketch (spec v2).

Implements stages 1-3 of ``ops/minhash.py``'s sketch — shingle hashing,
value-keyed survivor sampling, segment-min compaction — as ONE fused
kernel that reads each ingested byte exactly once.  The XLA formulation
pays ~20 HBM-bound vector ops per byte just to materialize the shingle
hashes;
this kernel keeps everything in registers and emits only the tiny
``(8, 128)`` survivor plane per chunk.

Layout: one chunk per grid step while its plane fits one block, and
``_BLOCK_ROWS`` rows of it a step beyond that (rows over 64 KiB: the
grid's second axis walks the chunk and the survivor plane is revisited,
one min a step).  The chunk's bytes are viewed as a
``(R, 128)`` plane of little-endian uint32 words (position-major:
word ``q`` sits at row ``q // 128``, lane ``q % 128``).  Byte windows
are rebuilt from aligned words only — each shingle phase ``r`` (byte
offset mod 4) combines a word with its successor ``W1``, so no
byte-misaligned loads exist anywhere.  ``W1`` itself is two lane/sublane
rotations plus a select.

Unsigned-min legalization: Mosaic has no vector ``arith.minui``, so the
running minima are kept in int32 with the bias trick
(``min_u(x, y) == min_s(x ^ 0x80000000, y ^ 0x80000000) ^ 0x80000000``);
the caller un-biases with one XLA xor.

Stage 4 (the P-way permutation over the ~256 survivors) is shared
verbatim with the XLA reference (``minhash_signature``) — it touches
1/256th of the data, so it is not worth a kernel, and sharing the code
makes bit-exactness of the full pipeline structural rather than
incidental.  Enforced by tests/test_pallas_kernels.py (interpret mode on
CPU, the real kernel on TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fastdfs_tpu.ops.minhash import (DEFAULT_PERMS, DEFAULT_SHINGLE, EMPTY,
                                     NUM_SEGMENTS, SAMPLE_MASK, _POLY_B,
                                     minhash_signature)

LANE = 128
_BIAS = np.int32(np.uint32(0x80000000).astype(np.int64) - (1 << 32))  # -2^31


# Rows of the word plane a grid step holds at the most: 64 KiB of chunk,
# the widest block the shipped widths ever had.
_BLOCK_ROWS = 128


def _survivor_kernel(k: int, R: int, steps: int):
    """Kernel over ``R`` rows of one chunk's word plane, step ``j`` of
    ``steps``: words (1, R, 128) u32 + len → biased survivor plane
    (1, 8, 128) i32, the min over the steps.  With more than one step the
    first 8 rows of the next block come in as a halo (the successor of a
    block's last word)."""
    if k != 5:
        raise NotImplementedError("survivor kernel is specialized to k=5")

    def kernel(lens_ref, w_ref, *rest):
        out_ref = rest[-1]
        W = w_ref[0]                                   # (R, 128) uint32
        ln = lens_ref[pl.program_id(0)]
        j = pl.program_id(1)

        # W1[q] = W[q+1] in flattened row-major word order: lane roll -1,
        # with lane 127 taking the next row's lane 0 (row+lane roll).
        nxt = W[:1, :] if steps == 1 else rest[0][0][:1, :]
        r1 = jnp.concatenate([W[:, 1:], W[:, :1]], axis=1)
        rr = jnp.concatenate([W[1:, :], nxt], axis=0)
        r01 = jnp.concatenate([rr[:, 1:], rr[:, :1]], axis=1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, LANE), 1)
        W1 = jnp.where(lane < LANE - 1, r1, r01)
        # Wrapped garbage in the last word's windows only reaches
        # positions p >= 4*NW - 4 > len - k, which the mask excludes.

        row = jax.lax.broadcasted_iota(jnp.int32, (R, LANE), 0) + j * R
        q4 = (row * LANE + lane) * 4                   # byte position of r=0
        # Valid positions are p <= bound (scalar select only: Mosaic has no
        # vector-of-bool select): complete shingles, or the degenerate
        # hash-the-padded-window rule for chunks shorter than k.
        bound = jnp.where(ln >= k, ln - k, jnp.maximum(ln, 1) - 1)
        B = _POLY_B
        m = jnp.full((R, LANE), 0x7FFFFFFF, dtype=jnp.int32)
        for r in range(4):
            if r == 0:
                x = W
                b4 = W1 & jnp.uint32(0xFF)
            else:
                x = (W >> jnp.uint32(8 * r)) | (W1 << jnp.uint32(32 - 8 * r))
                b4 = (W1 >> jnp.uint32(8 * r)) & jnp.uint32(0xFF)
            h = x & jnp.uint32(0xFF)
            h = h * B + ((x >> jnp.uint32(8)) & jnp.uint32(0xFF))
            h = h * B + ((x >> jnp.uint32(16)) & jnp.uint32(0xFF))
            h = h * B + (x >> jnp.uint32(24))
            h = h * B + b4
            p = q4 + r
            surv = (p <= bound) & ((h & jnp.uint32(SAMPLE_MASK)) == 0)
            hb = h.astype(jnp.int32) ^ _BIAS           # biased unsigned order
            m = jnp.minimum(m, jnp.where(surv, hb, jnp.int32(0x7FFFFFFF)))

        # segment = word q mod NUM_SEGMENTS = 128 * (row mod 8) + lane
        # (R is a multiple of 8, so a block's rows keep their residues).
        m = jnp.min(m.reshape(R // 8, 8, LANE), axis=0)
        if steps == 1:
            out_ref[0] = m
        else:
            @pl.when(j == 0)
            def _():
                out_ref[0] = m

            @pl.when(j > 0)
            def _():
                out_ref[0] = jnp.minimum(out_ref[0], m)

    return kernel


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def survivor_segmin_pallas(data, lengths, k: int = DEFAULT_SHINGLE,
                           interpret: bool = False):
    """Pallas twin of ops.minhash.survivor_segmin: uint8 (N, L) + int32 (N,)
    → uint32 (N, NUM_SEGMENTS), bit-identical.

    CONTRACT (shared with sha1_batch): rows are zero past their length.
    ``data`` may be the rows' little-endian words, uint32 (N, L / 4), as
    for ``sha1_batch_pallas``.
    """
    lengths = jnp.asarray(lengths, dtype=jnp.int32)
    as_words = getattr(data, "dtype", None) == jnp.uint32
    data = jnp.asarray(data, dtype=jnp.uint32 if as_words else jnp.uint8)
    n = data.shape[0]
    L = data.shape[1] * (4 if as_words else 1)
    block = 4 * NUM_SEGMENTS
    if L > _BLOCK_ROWS * LANE * 4:     # whole steps of _BLOCK_ROWS rows
        block = _BLOCK_ROWS * LANE * 4
    pad = (-L) % block
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad // 4 if as_words else pad)))
    NW = (L + pad) // 4
    rows = NW // LANE                                   # multiple of 8
    R = min(rows, _BLOCK_ROWS)
    steps = rows // R
    if as_words:
        words = data.reshape(n, rows, LANE)
    else:
        words = jax.lax.bitcast_convert_type(
            data.reshape(n, rows, LANE, 4), jnp.uint32)  # (N, rows, 128)

    in_specs = [pl.BlockSpec((1, R, LANE), lambda i, j, lens_ref: (i, j, 0))]
    operands = [words]
    if steps > 1:
        # The next block's first 8 rows; past the chunk's end any rows
        # do (see the kernel's note on the last word).
        in_specs.append(pl.BlockSpec(
            (1, 8, LANE), lambda i, j, lens_ref: (
                i, jnp.minimum((j + 1) * (R // 8), rows // 8 - 1), 0)))
        operands.append(words)
    out = pl.pallas_call(
        _survivor_kernel(k, R, steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 8, LANE),
                                   lambda i, j, lens_ref: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, 8, LANE), jnp.int32),
        interpret=interpret,
    )(lengths, *operands)
    z = jax.lax.bitcast_convert_type(out, jnp.uint32) ^ jnp.uint32(0x80000000)
    return z.reshape(n, NUM_SEGMENTS)


@functools.partial(jax.jit, static_argnames=("num_perms", "k", "interpret"))
def minhash_batch_pallas(data, lengths, num_perms: int = DEFAULT_PERMS,
                         k: int = DEFAULT_SHINGLE, interpret: bool = False):
    """Pallas-path twin of ops.minhash.minhash_batch: uint8 (N, L) +
    int32 (N,) → uint32 (N, num_perms) signatures (bit-identical)."""
    z = survivor_segmin_pallas(data, lengths, k, interpret)
    return jax.vmap(
        lambda zr: minhash_signature(zr, num_perms, zr != EMPTY))(z)
