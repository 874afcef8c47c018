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

A tile of fewer than 128 chunks (the wide widths' tiles: a tile is
bounded in bytes, so rows of 512 KiB and more come a few at a time)
takes a second layout, ``_sha1_rows_kernel``: lanes padded to 128 in HBM
would multiply such a tile by 128 / rows, so its words stay row-major
(chunk on the sublane axis, 128 words = 8 blocks a grid step) and the
kernel transposes each (rows, 128) block in VMEM.  The rounds are the
same code (``_compress``).  Its launch ends at its longest chunk: rows
of megabytes are few, a step costs the same whatever its lanes carry,
and a tile's width is a pow2 above what its chunks need.

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
from jax.experimental.pallas import tpu as pltpu

_H0 = np.array([0x67452301, 0xEFCDAB89, 0x98BADCFE, 0x10325476, 0xC3D2E1F0],
               dtype=np.uint32)
_K = np.array([0x5A827999, 0x6ED9EBA1, 0x8F1BBCDC, 0xCA62C1D6], dtype=np.uint32)

LANE = 128
DEFAULT_SUB = 16  # 2048 chunks per tile; wider amortizes instruction issue


def _rotl(x, n):
    return (x << jnp.uint32(n)) | (x >> jnp.uint32(32 - n))


def _compress(state, w):
    """One SHA-1 block: ``state`` five vregs, ``w`` its 16 message words
    (same shape); returns the five sums a0 + a .. e0 + e."""
    w = list(w)
    for t in range(16, 80):
        w.append(_rotl(w[t - 3] ^ w[t - 8] ^ w[t - 14] ^ w[t - 16], 1))
    a, bb, c, d, e = state
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
    return [s0 + s1 for s0, s1 in zip(state, (a, bb, c, d, e))]


def _sha1_kernel(words_ref, nblocks_ref, state_ref):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _():
        for i in range(5):
            state_ref[i, 0] = jnp.full(state_ref.shape[2:], _H0[i],
                                       dtype=jnp.uint32)

    # Message schedule: 16 loaded + 64 derived words, all (SUB,128) vregs.
    old = [state_ref[i, 0] for i in range(5)]
    upd = _compress(old, [words_ref[0, 0, t] for t in range(16)])
    # Blocks past a chunk's own padded length leave its state untouched.
    active = b < nblocks_ref[0]
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


_GROUP = 8  # SHA-1 blocks a grid step of the row-major kernel: 128 words


def _sha1_rows_kernel(words_ref, nblocks_ref, state_ref, rows_ref, wt_ref):
    """One grid step = ``_GROUP`` blocks of every chunk.  words_ref is a
    (rows, 128) block, chunk on the sublane axis; its transpose puts the
    chunk on the lane axis, where the rounds want it: row ``16 * s + t``
    of ``wt_ref`` is word ``t`` of the step's block ``s``."""
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _():
        for i in range(5):
            state_ref[i] = jnp.full((1, LANE), _H0[i], dtype=jnp.uint32)

    # Rows past the tile's own are whatever the scratch held: their lanes
    # never become active (nblocks is 0 there) and are cut off outside.
    rows_ref[pl.ds(0, words_ref.shape[0]), :] = words_ref[...]
    wt_ref[...] = rows_ref[...].T
    nblk = nblocks_ref[...]

    def block(s, state):
        base = pl.multiple_of(s * 16, 16)
        upd = _compress(list(state),
                        [wt_ref[pl.ds(base + t, 1), :] for t in range(16)])
        active = g * _GROUP + s < nblk
        return tuple(jnp.where(active, u, o) for u, o in zip(upd, state))

    state = jax.lax.fori_loop(0, _GROUP, block,
                              tuple(state_ref[i] for i in range(5)))
    for i in range(5):
        state_ref[i] = state[i]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sha1_rows_pallas(words, nblocks, interpret: bool = False):
    """words: (rows, groups * 128) uint32, row-major as the packing pass
    leaves them, rows a multiple of 8 and at most 128; nblocks: (1, 128)
    int32, chunk on the lane axis → state (5, 1, 128) uint32.

    The launch ends at its longest chunk: the grid's bound is the groups
    that chunk has, a value computed here on the device and not a static
    argument, so the program is one a shape whatever the lengths.  Every
    step it leaves out had all lanes masked off.  (The other form, the
    full grid with a scalar-prefetch operand, the index map clamped to
    the last live group and the body under ``pl.when``, reads the same
    bits and 0.05-0.09 us a skipped step on the v5e: PERF.md section 6,
    PR 38.)"""
    rows, n_words = words.shape
    live = -(-jnp.max(nblocks) // _GROUP)   # >= 1: an empty row has a block
    return pl.pallas_call(
        _sha1_rows_kernel,
        grid=(live,),
        in_specs=[pl.BlockSpec((rows, LANE), lambda g: (0, g)),
                  pl.BlockSpec((1, LANE), lambda g: (0, 0))],
        out_specs=pl.BlockSpec((5, 1, LANE), lambda g: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((5, 1, LANE), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((LANE, LANE), jnp.uint32),
                        pltpu.VMEM((LANE, LANE), jnp.uint32)],
        interpret=interpret,
    )(words, nblocks)


def default_sub(rows: int) -> int:
    """Sublanes of the lane-major kernel's vreg tile for a tile of
    ``rows`` chunks: whole groups of 128 lanes, 16 at the most."""
    return max(1, min(DEFAULT_SUB, rows // LANE))


def launch_geometry(rows: int, max_len: int, longest: int | None = None
                    ) -> tuple[int, int]:
    """``(lanes, blocks)`` of one ``sha1_batch_pallas`` call at
    ``default_sub(rows)``: the lanes its rounds run over (the rows after
    the kernel's padding) and the 64-byte blocks it walks one after
    another.  Under 128 rows the walk ends at the ``longest`` row's last
    block, in whole groups of ``_GROUP``; the lane-major kernel walks the
    width ``max_len`` whatever the rows hold, and so does either with no
    ``longest`` given (what the walk was before it ended early: the
    engine keeps both sums)."""
    max_blocks = (max_len + 8) // 64 + 1
    if rows < LANE:
        if longest is not None:
            max_blocks = (longest + 8) // 64 + 1
        return LANE, -(-max_blocks // _GROUP) * _GROUP
    tile = default_sub(rows) * LANE
    return -(-rows // tile) * tile, -(-rows // tile) * max_blocks


@functools.partial(jax.jit, static_argnames=("max_len", "sub", "interpret"))
def sha1_batch_pallas(data, lengths, max_len: int, sub: int = DEFAULT_SUB,
                      interpret: bool = False):
    """Pallas-path twin of ops.sha1._sha1_padded: uint8 (N, L) + int32 (N,)
    → uint32 (N, 5) digests.  Fewer than 128 rows take the row-major
    kernel (``sub`` is then unused), 128 and more the lane-major one.

    ``data`` may also be the rows as uint32 (N, L / 4), the host's view of
    the same bytes (``ndarray.view``, little-endian words): what the
    engine passes, because packing bytes into words on the device costs
    four bytes a byte and a relayout that pads the rows to 128.

    CONTRACT (same as sha1_batch): rows must be zero past their length —
    the padding pass relies on it to skip a full-array masking pass.
    """
    n = data.shape[0]
    by_rows = n < LANE
    max_blocks = (max_len + 8) // 64 + 1
    if by_rows:     # whole grid steps; the blocks added are never active
        max_blocks = -(-max_blocks // _GROUP) * _GROUP
    n_words = max_blocks * 16

    if data.dtype == jnp.uint8:
        buf = jnp.pad(data, ((0, 0), (0, n_words * 4 - data.shape[1])))
        le = jax.lax.bitcast_convert_type(buf.reshape(n, n_words, 4),
                                          jnp.uint32)
    else:
        le = jnp.pad(data, ((0, 0), (0, n_words - data.shape[1])))
    # Little-endian words → big-endian ones, then the padding, all on
    # words and in one pass: 0x80 into the byte after the message, and the
    # 64-bit big-endian bit length into the final block's last two words
    # (zero until then: they lie past the message).
    words = (((le & jnp.uint32(0xFF)) << 24) |
             ((le & jnp.uint32(0xFF00)) << 8) |
             ((le >> 8) & jnp.uint32(0xFF00)) |
             (le >> 24))  # (N, B * 16)
    idx = jnp.arange(n_words, dtype=jnp.int32)[None, :]
    lens = lengths.astype(jnp.int32)[:, None]
    nblk = (lens + 8) // 64 + 1
    mark = jnp.uint32(0x80) << (24 - 8 * (lens & 3)).astype(jnp.uint32)
    words = words | jnp.where(idx == lens >> 2, mark, jnp.uint32(0))
    ulen = lens.astype(jnp.uint32)
    words = jnp.where(idx == nblk * 16 - 2, ulen >> 29,
                      jnp.where(idx == nblk * 16 - 1, ulen << 3, words))

    if by_rows:
        # Row-major, as packed: the rows to whole sublane groups of 8 and
        # nothing else (lanes padded to 128 here would multiply a wide
        # tile by 128 / N in HBM, and again in a transpose).
        words = jnp.pad(words, ((0, (-n) % 8), (0, 0)))
        nblk_lane = jnp.pad(nblk[:, 0], (0, LANE - n))[None, :]
        state = _sha1_rows_pallas(words, nblk_lane, interpret)
        return state.reshape(5, LANE).T[:n]

    # Pad the chunk axis to whole (sub,128) tiles; dummies run 1 block.
    tile = sub * LANE
    n_pad = (-n) % tile
    if n_pad:
        words = jnp.pad(words, ((0, n_pad), (0, 0)))
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
