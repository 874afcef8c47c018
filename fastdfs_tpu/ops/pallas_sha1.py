"""Pallas TPU kernel for batched SHA1.

Why a kernel at all: the pure-XLA formulation in ``ops/sha1.py`` emits
~1000 elementwise HLO ops per 64-byte block whose intermediates spill to
HBM.  This kernel keeps the
five state words and the 80-entry message schedule in vector registers,
so steady-state cost collapses to one streamed read of the message plus
the VPU rounds (end-to-end throughput is then bounded by the XLA-side
padding/layout passes).  Neither path's rate is measured on this
machine yet (PERF.md).

Layout: chunks are packed one-per-lane onto (SUB, 128) vreg tiles —
SUB*128 chunks per grid step, so every round instruction advances
SUB*128 chunks at once.  The grid is ``(chunk_tiles, blocks)``; the block
axis iterates sequentially (TPU grid order) over one revisited state
accumulator per tile, so a tile's state never leaves VMEM between its
blocks.  Chunks with fewer blocks than the tile's max are masked per
block, which lets variable-length chunks share one fixed-shape launch.

Bit-exactness vs hashlib and vs the XLA reference is enforced by
tests/test_pallas_kernels.py (interpret mode on CPU; the real kernel
runs on the TPU sidecar via DedupEngine._fingerprint_batch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_H0 = np.array([0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476, 0xC3D2E1F0],
               dtype=np.uint32)
_K = np.array([0x5A827999, 0x6ED9EBA1, 0x8F1BBCDC, 0xCA62C1D6], dtype=np.uint32)

LANE = 128
DEFAULT_SUB = 16  # 2048 chunks per tile; wider amortizes instruction issue


def _rotl(x, n):
    return (x << jnp.uint32(n)) | (x >> jnp.uint32(32 - n))


def _sha1_kernel(words_ref, nblocks_ref, state_ref):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _():
        for i in range(5):
            state_ref[i, 0] = jnp.full(state_ref.shape[2:], _H0[i],
                                       dtype=jnp.uint32)

    # Message schedule: 16 loaded + 64 derived words, all (SUB,128) vregs.
    w = [words_ref[0, 0, t] for t in range(16)]
    for t in range(16, 80):
        w.append(_rotl(w[t - 3] ^ w[t - 8] ^ w[t - 14] ^ w[t - 16], 1))

    a = state_ref[0, 0]
    bb = state_ref[1, 0]
    c = state_ref[2, 0]
    d = state_ref[3, 0]
    e = state_ref[4, 0]
    a0, b0, c0, d0, e0 = a, bb, c, d, e
    for t in range(80):
        if t < 20:
            f = (bb & c) | (~bb & d)
        elif t < 40:
            f = bb ^ c ^ d
        elif t < 60:
            f = (bb & c) | (bb & d) | (c & d)
        else:
            f = bb ^ c ^ d
        tmp = _rotl(a, 5) + f + e + jnp.uint32(_K[t // 20]) + w[t]
        a, bb, c, d, e = tmp, a, _rotl(bb, 30), c, d

    # Blocks past a chunk's own padded length leave its state untouched.
    active = b < nblocks_ref[0]
    upd = [a0 + a, b0 + bb, c0 + c, d0 + d, e0 + e]
    old = [a0, b0, c0, d0, e0]
    for i in range(5):
        state_ref[i, 0] = jnp.where(active, upd[i], old[i])


@functools.partial(jax.jit, static_argnames=("max_blocks", "sub", "interpret"))
def _sha1_pallas(words, nblocks, max_blocks: int, sub: int,
                 interpret: bool = False):
    """words: (T, max_blocks, 16, sub, 128) uint32 — a (tile, block) slice
    is one contiguous read, so the pipeline overlaps a single DMA per
    step; nblocks: (T, sub, 128) int32 → state (5, T, sub, 128) uint32."""
    n_tiles = words.shape[0]
    return pl.pallas_call(
        _sha1_kernel,
        grid=(n_tiles, max_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, 16, sub, LANE),
                         lambda i, b: (i, b, 0, 0, 0)),
            pl.BlockSpec((1, sub, LANE), lambda i, b: (i, 0, 0)),
        ],
        # Revisited across the (sequential) block axis: one tile's state
        # stays resident in VMEM for all of its blocks.
        out_specs=pl.BlockSpec((5, 1, sub, LANE), lambda i, b: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((5, n_tiles, sub, LANE), jnp.uint32),
        interpret=interpret,
    )(words, nblocks)


@functools.partial(jax.jit, static_argnames=("max_len", "sub", "interpret"))
def sha1_batch_pallas(data, lengths, max_len: int, sub: int = DEFAULT_SUB,
                      interpret: bool = False):
    """Pallas-path twin of ops.sha1._sha1_padded: uint8 (N, L) + int32 (N,)
    → uint32 (N, 5) digests.

    CONTRACT (same as sha1_batch): rows must be zero past their length —
    the padding pass relies on it to skip a full-array masking pass.
    """
    n = data.shape[0]
    max_blocks = (max_len + 8) // 64 + 1
    padded_len = max_blocks * 64

    buf = jnp.pad(data, ((0, 0), (0, padded_len - data.shape[1])))
    idx = jnp.arange(padded_len, dtype=jnp.int32)[None, :]
    lens = lengths.astype(jnp.int32)[:, None]
    nblk = (lens + 8) // 64 + 1
    msg_end = nblk * 64
    buf = jnp.where(idx == lens, jnp.uint8(0x80), buf)

    # 64-bit big-endian bit length in the last 8 bytes of the final block.
    bitlen_lo = lens.astype(jnp.uint32) << 3
    bitlen_hi = lens.astype(jnp.uint32) >> 29
    byte_pos = idx - (msg_end - 8)
    in_field = (byte_pos >= 0) & (byte_pos < 8)
    shift = jnp.where(byte_pos < 4, (3 - jnp.clip(byte_pos, 0, 3)) * 8,
                      (7 - jnp.clip(byte_pos, 4, 7)) * 8).astype(jnp.uint32)
    word = jnp.where(byte_pos < 4, bitlen_hi, bitlen_lo)
    len_byte = ((word >> shift) & jnp.uint32(0xFF)).astype(jnp.uint8)
    buf = jnp.where(in_field, len_byte, buf)

    # Bytes → big-endian words via one bitcast + a word-level byteswap
    # (4x fewer elements than shifting four byte planes together).
    le = jax.lax.bitcast_convert_type(
        buf.reshape(n, max_blocks, 16, 4), jnp.uint32)
    words = (((le & jnp.uint32(0xFF)) << 24) |
             ((le & jnp.uint32(0xFF00)) << 8) |
             ((le >> 8) & jnp.uint32(0xFF00)) |
             (le >> 24))  # (N, B, 16)

    # Pad the chunk axis to whole (sub,128) tiles; dummies run 1 block.
    tile = sub * LANE
    n_pad = (-n) % tile
    if n_pad:
        words = jnp.pad(words, ((0, n_pad), (0, 0), (0, 0)))
        nblk_full = jnp.concatenate(
            [nblk[:, 0], jnp.ones((n_pad,), jnp.int32)])
    else:
        nblk_full = nblk[:, 0]
    n_tiles = (n + n_pad) // tile

    # (N, B, 16) -> (T, B, 16, sub, 128): chunk n -> tile n//tile,
    # sublane (n%tile)//128, lane n%128; a (tile, block) slice is
    # contiguous.
    words_t = (words.reshape(n_tiles, sub, LANE, max_blocks, 16)
               .transpose(0, 3, 4, 1, 2))
    nblk_t = nblk_full.reshape(n_tiles, sub, LANE)
    state = _sha1_pallas(words_t, nblk_t, max_blocks, sub, interpret)
    return state.reshape(5, -1).T[:n]  # (N, 5)
