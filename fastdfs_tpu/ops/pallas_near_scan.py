"""The near-duplicate index's scan as a Pallas kernel: one read of the
whole signature matrix for every query of a pass.

The matrix is lane-major, ``(P, capacity / LANES, LANES)`` uint32: the
signature of row r is ``[:, r // LANES, r % LANES]``, so for one
permutation p a vector register holds 1,024 rows and a query's lane p is
one scalar.  A grid step takes a block of ``BLOCK = SUB * LANES`` rows
(all P permutations of it, 4 MiB) and, for each query, ORs the XOR of a
band's lanes with the query's: a row shares the whole band iff that is
zero.  Per query and block it writes the candidates folded to one
``(8, 128)`` tile; ``near_scan_pallas`` reduces the tiles to one bit a
query a block.  The kernel nominates blocks and nothing else: whether a
candidate is live, its score and the threshold are the rank program's
(``dedup/near_index.py``), which reads only the nominated blocks.

Why a kernel: XLA splits the 64-lane comparison over several fusions with
``(Q, capacity)`` temporaries between them, 5.7 GB at one query over 30M
rows and more than the chip holds at eight (compiled here for a described
v5e; PERF.md section 6, PR 39).  ``near_scan_xla`` is the same function in
plain ``jax.numpy``: the reference the kernel is held to
(tests/test_pallas_kernels.py) and what a CPU backend runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Pallas itself is imported where the kernel is traced: this module's
# constants are read by every process that imports the sidecar (the
# benchmark's harness among them), the kernel only by one that is asked.
LANES = 1024
SUB = 16
BLOCK = SUB * LANES       # rows a grid step reads, and a result bit covers


def _kernel(bands: int, perms: int):
    per_band = perms // bands

    def kernel(q_ref, sigs_ref, out_ref):
        def one_query(q, carry):
            cand = None
            for b in range(bands):
                differ = None
                for p in range(b * per_band, (b + 1) * per_band):
                    x = sigs_ref[p] ^ q_ref[q * perms + p].astype(jnp.uint32)
                    differ = x if differ is None else differ | x
                whole = differ == 0
                cand = whole if cand is None else cand | whole
            found = cand.astype(jnp.int32)                  # (SUB, LANES)
            tile = None
            for i in range(0, SUB, 8):
                for j in range(0, LANES, 128):
                    part = found[i:i + 8, j:j + 128]
                    tile = part if tile is None else tile | part
            out_ref[0, q] = tile
            return carry

        jax.lax.fori_loop(0, out_ref.shape[1], one_query, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("bands", "interpret"))
def near_scan_pallas(sigs_t, queries, bands: int = 16,
                     interpret: bool = False):
    """``sigs_t`` ``(P, capacity / LANES, LANES)`` uint32, ``queries``
    ``(Q, P)`` uint32 -> ``(Q, capacity / BLOCK)`` bool: the blocks that
    hold a row sharing one whole band with the query."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    perms, subs, _ = sigs_t.shape
    n_q, blocks = queries.shape[0], subs // SUB
    flat = jax.lax.bitcast_convert_type(queries, jnp.int32).reshape(-1)
    tiles = pl.pallas_call(
        _kernel(bands, perms),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[pl.BlockSpec((perms, SUB, LANES),
                                   lambda i, q_ref: (0, i, 0))],
            out_specs=pl.BlockSpec((1, n_q, 8, 128),
                                   lambda i, q_ref: (i, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((blocks, n_q, 8, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
    )(flat, sigs_t)
    return (tiles != 0).any(axis=(2, 3)).T


@functools.partial(jax.jit, static_argnames=("bands",))
def near_scan_xla(sigs_t, queries, bands: int = 16):
    """``near_scan_pallas`` in plain ``jax.numpy``."""
    perms, subs, _ = sigs_t.shape
    per_band = perms // bands
    cand = None
    for b in range(bands):
        differ = None
        for p in range(b * per_band, (b + 1) * per_band):
            x = sigs_t[p][None] ^ queries[:, p][:, None, None]
            differ = x if differ is None else differ | x
        whole = differ == 0
        cand = whole if cand is None else cand | whole
    return cand.reshape(queries.shape[0], subs // SUB, BLOCK).any(axis=2)
