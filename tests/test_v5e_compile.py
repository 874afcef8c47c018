"""The fingerprint path's device programs, compiled for a v5e that is
described and not attached.

Interpret mode (tests/test_pallas_kernels.py) proves the kernels compute
the right bits; it cannot show what Mosaic refuses: a slice off the
tiling, too much VMEM, a kernel that cannot be partitioned.  The TPU
compiler is installed here and compiles for a described topology, so
these tests ask it, at the widths the sidecar really runs: row_tile 256
at the smallest and the largest pow2 length bucket and the smaller tiles
of the plan (engine.plan_shapes) at their narrowest and widest, and the
tiles of a backup node's widths (512 KiB - 8 MiB: a few rows of
megabytes, bounded in bytes) at both ends, rows as the words the engine
passes, both Pallas kernels, the jitted result concat, the XLA SHA-1 the scrubber's DEDUP_VERIFY
jits, and the four-device fan-out step.  Nothing executes, so they say
nothing about results or speed (chip_smoke.py does, on the chip).

ALL such tests live in this ONE file: the worker that runs it loads
libtpu and keeps its lock until it exits, and a second file could land
on another worker.  The topology is described inside a module fixture,
never at import (every xdist worker imports every test file).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from fastdfs_tpu.dedup.engine import (DedupConfig, _packed_concat,
                                       plan_shapes)
from fastdfs_tpu.ops.pallas_minhash import minhash_batch_pallas
from fastdfs_tpu.ops.pallas_sha1 import default_sub, sha1_batch_pallas
from fastdfs_tpu.ops.sha1 import _sha1_padded
from fastdfs_tpu.parallel.ingest_step import make_fingerprint_step

CFG = DedupConfig()
ROWS = CFG.row_tile
SMALLEST, LARGEST = CFG.min_size, CFG.max_size
# The full tile at both ends of the widths, and the tiles under row_tile
# that the plan ships for sparse buckets, the narrowest and the widest.
_SMALL = [shape for shape in plan_shapes(CFG) if shape[0] < ROWS]
# The same at the widths backup tools cut (restic_chunks): the one tile
# of 128 rows (lane-major kernel, 64 MiB), the narrowest small one and
# the widest (row-major kernel; 8 rows of 8 MiB).
_WIDE = plan_shapes(DedupConfig(min_size=512 << 10, avg_bits=20,
                                max_size=8 << 20))
TILES = [(ROWS, SMALLEST), (ROWS, LARGEST), _SMALL[0], _SMALL[-1],
         _WIDE[0], _WIDE[1], _WIDE[-1]]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any reason it cannot is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the chip: the next run would warn
    # and compile again.  Keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _batch(rows, blen, sharding, lens_sharding=None, words=False):
    """A tile as bytes, or (``words``) as the uint32 view of the staging
    buffer that engine.py:_fingerprint_batch hands the Pallas kernels."""
    shape, dtype = ((rows, blen // 4), jnp.uint32) if words else (
        (rows, blen), jnp.uint8)
    return (jax.ShapeDtypeStruct(shape, dtype, sharding=sharding),
            jax.ShapeDtypeStruct((rows,), jnp.int32,
                                 sharding=lens_sharding or sharding))


@pytest.mark.parametrize("rows,blen", TILES)
def test_sha1_pallas_compiles_for_v5e(one_chip, rows, blen):
    data, lens = _batch(rows, blen, one_chip, words=True)
    compiled = sha1_batch_pallas.lower(data, lens, max_len=blen,
                                       sub=default_sub(rows)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # under 128 rows the row-major kernel: no lane padding in HBM
    assert ("_sha1_rows_pallas" in text) == (rows < 128)
    # the packed words and nothing much else: a tile is never multiplied
    # by 128 / rows on the device
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.5 * rows * blen


# The row-major shapes at both ends of both sets of widths (in TILES) and
# the two the walk-aware plan ships most at the restic widths: the launch
# ends at its longest row by a grid bound that is a run-time value, which
# interpret mode accepts whatever Mosaic would say.
_ROW_MAJOR = sorted({s for s in (_SMALL[0], _SMALL[-1], _WIDE[1], _WIDE[-1],
                                 (32, 2 << 20), (16, 4 << 20))})


@pytest.mark.parametrize("rows,blen", _ROW_MAJOR)
def test_sha1_row_major_launch_bound_is_a_run_time_value(one_chip, rows,
                                                         blen):
    """One program a shape: the longest row is not a static argument, so
    the lowering takes shapes alone, and Mosaic takes a kernel whose grid
    is bounded by a value computed on the device."""
    assert (rows, blen) in plan_shapes(CFG) + _WIDE and rows < 128
    data, lens = _batch(rows, blen, one_chip, words=True)
    jaxpr = jax.make_jaxpr(lambda d, n: sha1_batch_pallas(
        d, n, max_len=blen, sub=default_sub(rows)))(data, lens)
    (grid,) = _pallas_grids(jaxpr.jaxpr)
    assert len(grid) == 1 and not isinstance(grid[0], int)
    text = sha1_batch_pallas.lower(data, lens, max_len=blen,
                                   sub=default_sub(rows)).compile().as_text()
    assert "_sha1_rows_pallas" in text and "tpu_custom_call" in text


def _pallas_grids(jaxpr):
    """The grid of every pallas_call under ``jaxpr``."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


@pytest.mark.parametrize("rows,blen", TILES)
def test_minhash_pallas_compiles_for_v5e(one_chip, rows, blen):
    data, lens = _batch(rows, blen, one_chip, words=True)
    compiled = minhash_batch_pallas.lower(
        data, lens, num_perms=CFG.num_perms, k=CFG.shingle).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes <= 2.5 * rows * blen


def _near_arrays(one_chip, n_q):
    """The near-dup index's device arrays at 30M rows and their eighth to
    spare (8.64 GB), and a pass's queries with their least counts and row
    limit (``DeviceNearIndex._asked``)."""
    from fastdfs_tpu.dedup.near_index import DeviceNearIndex
    from fastdfs_tpu.ops.pallas_near_scan import LANES

    capacity = DeviceNearIndex._capacity_for(30_000_000 + 30_000_000 // 8)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return capacity, (arg((64, capacity // LANES, LANES), jnp.uint32),
                      arg((capacity // LANES, LANES), jnp.bool_),
                      arg((n_q, 64 + 2), jnp.uint32))


@pytest.mark.parametrize("n_q", [1, 8])
def test_near_pass_compiles_for_v5e_at_a_nodes_size(one_chip, n_q):
    """A pass is one program: the scan, its nominated blocks picked and
    ranked on the device.  The kernel is there and the pass needs no
    temporary beside the matrix, so neither the compaction nor the rank's
    slices copy it (XLA's own fusion of the scan's comparison needs 5.7 GB
    at one query and more than the chip holds at eight, a gather of the
    blocks 2.2 GB: PERF.md section 6, PR 39)."""
    from fastdfs_tpu.dedup.near_index import _programs

    capacity, args = _near_arrays(one_chip, n_q)
    compiled = _programs(16, True)["scan"].lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= capacity * 256
    assert mem.temp_size_in_bytes <= 256 << 20


def test_near_spill_rank_and_insert_compile_for_v5e_at_a_nodes_size(one_chip):
    """The rank program alone (a pass's blocks beyond the first
    RANK_BLOCKS) gathers its blocks by slices (a gather copied the
    matrix: 2.2 GB of temporaries), and an insert writes in place."""
    from fastdfs_tpu.dedup.near_index import RANK_BLOCKS, _programs

    capacity, (sigs, live, asked) = _near_arrays(one_chip, 8)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    prog = _programs(16, True)
    rank = prog["rank"].lower(sigs, live, asked,
                              arg((RANK_BLOCKS,), jnp.int32)).compile()
    assert rank.memory_analysis().temp_size_in_bytes <= 256 << 20
    insert = prog["insert"].lower(sigs, live, arg((64,), jnp.uint32),
                                  arg((), jnp.int32),
                                  arg((), jnp.bool_)).compile()
    mem = insert.memory_analysis()
    assert mem.alias_size_in_bytes >= capacity * 256     # donated, in place
    assert mem.temp_size_in_bytes <= 1 << 20


def test_packed_concat_compiles_for_v5e(one_chip):
    rows = [ROWS] + [r for r, _ in _SMALL[-2:]]  # one segment's mixed tiles
    digests = [jax.ShapeDtypeStruct((r, 5), jnp.uint32, sharding=one_chip)
               for r in rows]
    sigs = [jax.ShapeDtypeStruct((r, CFG.num_perms), jnp.uint32,
                                 sharding=one_chip) for r in rows]
    compiled = _packed_concat(len(rows)).lower(*digests, *sigs).compile()
    assert compiled.output_shardings is not None


def test_verify_sha1_compiles_for_v5e(one_chip):
    """What DedupSidecar._batch_sha1 jits for a scrub batch: the XLA
    SHA-1 at (chunks in the batch, longest chunk) — here the daemon's
    largest batch, 64 chunks (scrub.cc kBatchChunks) of max_size."""
    data, lens = _batch(64, LARGEST, one_chip)
    _sha1_padded.lower(data, lens, max_len=LARGEST).compile()


def test_fanout_step_compiles_for_four_v5e_chips(topo):
    """engine.py routes every batch here when fan_out > 1 (the default on
    a four-chip host): rows sharded over a 1-D dp mesh, the XLA reference
    kernels under shard_map."""
    mesh = Mesh(np.array(topo.devices[:4]), ("dp",))
    data, lens = _batch(ROWS, LARGEST, NamedSharding(mesh, P("dp", None)),
                        NamedSharding(mesh, P("dp")))
    step = make_fingerprint_step(mesh, CFG.num_perms, CFG.shingle)
    compiled = step.lower(data, lens).compile()
    # Rows stay split: each device holds a quarter of the batch.
    per_dev = compiled.memory_analysis().argument_size_in_bytes
    assert per_dev < ROWS * LARGEST // 2
