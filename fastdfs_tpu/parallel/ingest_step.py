"""The distributed ingest step: the framework's "full training step".

One jitted ``shard_map`` over a (dp, sp, tp) mesh runs the whole upload
fingerprint pipeline with real shardings and collectives:

1. **CDC, sequence-parallel (sp)** — each device holds one contiguous block
   of every stream; the gear hash's 31-byte window straddles block seams,
   so each device ``ppermute``-sends its trailing window to the next
   device (ring halo exchange) and computes exact per-position hashes for
   its block.  Cut candidates come out bit-identical to the single-device
   path (tested).
2. **Fingerprints, data-parallel (dp)** — the chunk batch is row-sharded;
   each device runs batched SHA1 on its rows, then the digests are
   ``all_gather``-ed (the "cross-node digest all-gather" of BASELINE
   config 5).
3. **MinHash, tensor-parallel (tp)** — the permutation axis is sharded;
   each device computes ``P/tp`` signature lanes, reassembled with
   ``all_gather``.
4. **Index query (dp + pmax)** — the signature index is row-sharded over
   dp; every device scores the (gathered) query signatures against its
   shard and the global best similarity is reduced with ``pmax``.

There is no SGD here — a storage system's "step" is ingest — but the
sharding roles are the real ones: dp=batch, sp=sequence(byte stream),
tp=feature(hash lanes).  Pipeline parallelism is intentionally absent: the
reference's 5-stage upload pipeline (SURVEY.md §2.8) is an *async host*
pipeline (nio→dio→binlog→sync), which maps to overlapping host↔device
streams, not to device-staged layers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from fastdfs_tpu.dedup.near_index import band_scores
from fastdfs_tpu.ops.gear_cdc import GEAR_TABLE, WINDOW
from fastdfs_tpu.ops.minhash import (EMPTY, _perm_constants, minhash_batch,
                                     survivor_segmin)
from fastdfs_tpu.ops.sha1 import _sha1_padded

HALO = WINDOW - 1


def _gear_from_g(g: jax.Array) -> jax.Array:
    """Windowed gear hash over pre-gathered table values ``g`` (n,)."""
    h = g
    for k in range(1, WINDOW):
        shifted = jnp.roll(g, k).at[:k].set(0)
        h = h + (shifted << np.uint32(k))
    return h


def make_ingest_step(mesh: Mesh, num_perms: int = 64, avg_bits: int = 13,
                     shingle: int = 5):
    """Build the jitted distributed ingest step for ``mesh``.

    Returns ``step(stream, chunk_batch, chunk_lens, index_sigs)`` where

    - ``stream``: uint8 ``(B, sp, block_len)`` — B byte streams, each split
      into ``sp`` contiguous blocks (global stream = concat along axis 1);
    - ``chunk_batch``: uint8 ``(N, L)``; ``chunk_lens``: int32 ``(N,)``;
    - ``index_sigs``: uint32 ``(M, num_perms)`` — the near-dup index shard
      rows (M across dp);

    and returns ``(cand_mask (B, sp, block_len) bool, digests (N, 5),
    sigs (N, num_perms), best_sim (N,))``.
    """
    dp = mesh.shape["dp"]
    sp = mesh.shape["sp"]
    tp = mesh.shape["tp"]
    if num_perms % tp:
        raise ValueError(f"num_perms {num_perms} must divide by tp {tp}")
    p_local = num_perms // tp
    a_full, b_full = _perm_constants(num_perms)
    mask_val = np.uint32((1 << avg_bits) - 1)
    table = jnp.asarray(GEAR_TABLE)

    def step_local(stream, chunk_batch, chunk_lens, index_sigs):
        # ---- stage 1: sequence-parallel CDC with ring halo exchange -----
        # local stream: (B_loc, 1, block_len) — the sp axis is fully split.
        blk = stream[:, 0, :]                       # (B_loc, L_blk) uint8
        g = table[blk.astype(jnp.int32)]            # gear values
        tail = g[:, -HALO:]                         # my trailing window
        sp_idx = jax.lax.axis_index("sp")
        # ring: device i sends tail to i+1 (its successor holds the next block)
        prev_tail = jax.lax.ppermute(
            tail, "sp", [(i, (i + 1) % sp) for i in range(sp)])
        # first block has no predecessor: zero its halo contributions
        prev_tail = jnp.where(sp_idx == 0, jnp.uint32(0), prev_tail)
        g_ext = jnp.concatenate([prev_tail, g], axis=1)
        h = jax.vmap(_gear_from_g)(g_ext)[:, HALO:]  # (B_loc, L_blk)
        cand = ((h & mask_val) == 0)[:, None, :]     # restore the sp axis

        # ---- stage 2: data-parallel SHA1 + digest all-gather ------------
        digests_loc = _sha1_padded(chunk_batch, chunk_lens,
                                   int(chunk_batch.shape[1]))  # (N_loc, 5)
        digests = jax.lax.all_gather(digests_loc, "dp", axis=0, tiled=True)

        # ---- stage 3: tensor-parallel MinHash (v2 survivor sketch) ------
        tp_idx = jax.lax.axis_index("tp")
        a = jax.lax.dynamic_slice(jnp.asarray(a_full), (tp_idx * p_local,), (p_local,))
        b = jax.lax.dynamic_slice(jnp.asarray(b_full), (tp_idx * p_local,), (p_local,))

        z = survivor_segmin(chunk_batch, chunk_lens, shingle)  # (N_loc, S)

        def one_sig(zr):
            hv = zr[None, :] * a[:, None] + b[:, None]
            hv = jnp.where((zr != EMPTY)[None, :], hv, EMPTY)
            return hv.min(axis=1)                    # (p_local,)

        sigs_loc = jax.vmap(one_sig)(z)              # (N_loc, p_local)
        sigs_full = jax.lax.all_gather(sigs_loc, "tp", axis=1, tiled=True)
        sigs = jax.lax.all_gather(sigs_full, "dp", axis=0, tiled=True)  # (N, P)

        # ---- stage 4: dp-sharded index query + global pmax --------------
        # index_sigs local: (M_loc, P); score all N queries vs my shard.
        # The served index's scoring function (lane-major: a shard's rows
        # as columns); the agreeing lanes over P is the similarity.
        counts, _ = band_scores(index_sigs.T, sigs, bands=1)     # (N, M_loc)
        local_best = jnp.max(counts.astype(jnp.float32) / num_perms,
                             axis=1, initial=0.0)                # 0.0 if M_loc==0
        best = jax.lax.pmax(local_best, "dp")                    # (N,)
        return cand, digests, sigs, best

    sharded = jax.shard_map(
        step_local,
        mesh=mesh,
        in_specs=(P("dp", "sp", None), P("dp", None), P("dp"), P("dp", None)),
        out_specs=(P("dp", "sp", None), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded)


def fingerprint_mesh(n_devices: int | None = None) -> Mesh:
    """A 1-D ``dp`` mesh over the local devices, for the fingerprint
    fan-out (chunk rows are the abundant parallelism; no collectives are
    needed, so one axis is the whole story)."""
    devs = jax.local_devices()
    n = len(devs) if n_devices is None else n_devices
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), ("dp",))


def make_fingerprint_step(mesh: Mesh, num_perms: int = 64, shingle: int = 5):
    """Build the jitted multi-chip fingerprint step for a 1-D ``dp`` mesh.

    Returns ``step(chunk_batch (N, L) uint8, chunk_lens (N,) int32) ->
    (digests (N, 5) uint32, sigs (N, num_perms) uint32)``.  ``N`` must
    divide by ``mesh.shape['dp']``.

    This is the ingest hot loop's scale-out: rows shard across every
    local device and each chip runs batched SHA1 (``_sha1_padded``) plus
    the survivor-sketch MinHash (``minhash_batch``) on its slice — pure
    map parallelism, zero collectives, so aggregate throughput is
    ``n_devices x`` the per-chip rate minus transfer overlap.  Outputs
    stay sharded (``P('dp', None)``); fetching reassembles them.  Both
    kernels are the XLA references that the Pallas twins are pinned
    bit-identical to (tests/test_pallas_kernels.py), so the fan-out path
    produces byte-for-byte the digests/signatures of the single-chip
    paths — verified across mesh sizes in tests/test_cdc_kernels.py.
    """
    def fp_local(chunk_batch, chunk_lens):
        digests = _sha1_padded(chunk_batch, chunk_lens,
                               int(chunk_batch.shape[1]))
        sigs = minhash_batch(chunk_batch, chunk_lens, num_perms, shingle)
        return digests, sigs

    sharded = jax.shard_map(
        fp_local,
        mesh=mesh,
        in_specs=(P("dp", None), P("dp")),
        out_specs=(P("dp", None), P("dp", None)),
        check_vma=False,
    )
    return jax.jit(sharded)


@functools.cache
def _cached_fingerprint_step(mesh_key, num_perms, shingle):
    mesh, _ = mesh_key
    return make_fingerprint_step(mesh, num_perms, shingle)


def distributed_fingerprint(mesh: Mesh, chunk_batch, chunk_lens,
                            num_perms: int = 64, shingle: int = 5):
    """Convenience wrapper: build (cached) and run the fan-out step."""
    step = _cached_fingerprint_step(
        (mesh, str(mesh.devices.tolist())), num_perms, shingle)
    return step(jnp.asarray(chunk_batch, dtype=jnp.uint8),
                jnp.asarray(chunk_lens, dtype=jnp.int32))


@functools.cache
def _cached_step(mesh_key, num_perms, avg_bits, shingle):
    mesh, _ = mesh_key
    return make_ingest_step(mesh, num_perms, avg_bits, shingle)


def distributed_ingest_step(mesh: Mesh, stream, chunk_batch, chunk_lens,
                            index_sigs, num_perms: int = 64,
                            avg_bits: int = 13, shingle: int = 5):
    """Convenience wrapper: build (cached) and run the step on ``mesh``."""
    step = _cached_step((mesh, str(mesh.devices.tolist())), num_perms,
                        avg_bits, shingle)
    return step(jnp.asarray(stream, dtype=jnp.uint8),
                jnp.asarray(chunk_batch, dtype=jnp.uint8),
                jnp.asarray(chunk_lens, dtype=jnp.int32),
                jnp.asarray(index_sigs, dtype=jnp.uint32))
