"""The chunk widths as one setting (storage.conf ``dedup_cdc_widths``, the
sidecar's ``--cdc-widths``): the daemon cuts with them, the engine plans
tiles for them under a byte bound, both kernels run rows far wider than
the shipped 64 KiB, a daemon and a sidecar that disagree do not
fingerprint, and a snapshot from other widths is discarded.

Small widths keep it fast: 4 KiB / 2^13 / 64 KiB and 16 KiB / 2^15 /
256 KiB stand in for the 512 KiB / 2^20 / 8 MiB a backup node runs
(``benchmark/configs/restic_chunks.json``, whose CPU rehearsal is
``benchmark/tests/test_widths.py``, run with tier-1 through
``tests/test_benchmark_correct.py``).  The referees are the benchmark's
``reference.cuts_serial`` (a per-byte chunker that imports nothing of the
program), hashlib, and the NumPy MinHash of ``test_dedup_engine.py``.
"""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from fastdfs_tpu.dedup import engine as engine_mod
from fastdfs_tpu.dedup.engine import (DedupConfig, DedupEngine, plan_shapes,
                                       tile_plan)
from harness import (STORAGED, Sidecar, ensure_native_built, free_port,
                     make_storage_conf, start_storage, start_tracker)
from test_dedup_engine import _np_signature

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
import reference  # noqa: E402  — benchmark/reference.py
from run import RecipeReader  # noqa: E402  — benchmark/run.py

K, M = 1 << 10, 1 << 20
NARROW = (4 * K, 13, 64 * K)
WIDE = (16 * K, 15, 256 * K)
RESTIC = (512 * K, 20, 8 * M)

# What tile_plan of PR 38's parent made of seeded requests, as literal
# plans (the file says how it was generated): the shipped widths' must stay
# as they are, the restic widths' are what the walk-aware plan is held
# against.
with open(os.path.join(REPO, "tests", "goldens", "tile_plans_parent.json")) as f:
    PARENT_PLANS = json.load(f)


def _conf_text(widths) -> str:
    return "%d:%d:%d" % widths


def _seeded(n: int, seed: int) -> bytes:
    return np.random.default_rng(seed).bytes(n)


def _widths_dict(widths, segment=64 * M) -> dict:
    return {"cdc_min_size": widths[0], "cdc_avg_bits": widths[1],
            "cdc_max_size": widths[2], "shingle": 5, "num_perms": 64,
            "dedup_chunk_threshold": 64 * K, "dedup_segment_bytes": segment}


# -- the daemon's chunker ------------------------------------------------------

@pytest.mark.parametrize("widths,size", [(NARROW, 600 * K), (WIDE, 2 * M)])
def test_daemon_cuts_at_configured_widths_equal_the_serial_chunker(
        tmp_path, widths, size):
    from fastdfs_tpu.client.storage_client import StorageClient
    data = _seeded(size, 33) + bytes(widths[2] + 5)   # ends in a forced cut
    st = start_storage(str(tmp_path / "st"), dedup_mode="cpu",
                       extra=f"dedup_cdc_widths = {_conf_text(widths)}")
    try:
        with StorageClient(st.ip, st.port) as sc:
            fid = sc.upload_buffer(data, ext="bin")
            assert sc.download_to_buffer(fid) == data
        reader = RecipeReader(st.port)
        got, logical = reader.fetch(fid)
        reader.close()
    finally:
        st.stop()
    ends = reference.cuts_serial(data, *widths)
    want, last = [], 0
    for cut in ends:
        want.append((cut - last, hashlib.sha1(data[last:cut]).digest()))
        last = cut
    assert logical == len(data) and got == want
    assert max(n for n, _ in got) == widths[2]
    assert min(n for n, _ in got[:-1]) >= widths[0]


@pytest.mark.parametrize("text,why", [
    ("16:13:64K", "at least 32"),
    ("64K:13:2K", "under the maximum"),
    ("1M:20:128M", "dedup_segment_bytes"),
    ("2K:0:64K", "avg_bits"),
    ("2K,13,64K", "<min>:<avg_bits>:<max>"),
])
def test_daemon_refuses_widths_it_cannot_cut_with(tmp_path, text, why):
    ensure_native_built()
    conf = make_storage_conf(str(tmp_path), free_port(), dedup_mode="cpu",
                             extra=f"dedup_cdc_widths = {text}")
    proc = subprocess.run([STORAGED, conf], capture_output=True, text=True,
                          timeout=30)
    said = proc.stdout + proc.stderr
    assert proc.returncode != 0
    assert "dedup_cdc_widths" in said and why in said, said


# -- the engine at other widths ------------------------------------------------

@pytest.mark.parametrize("widths,size", [(NARROW, 500 * K), (WIDE, 3 * M)])
def test_engine_fingerprint_at_widths_equals_hashlib_and_numpy(widths, size):
    cfg = DedupConfig(min_size=widths[0], avg_bits=widths[1],
                      max_size=widths[2], use_pallas=False)
    data = _seeded(size, 34) + bytes(widths[2] + 3)
    spans, digests, sigs = DedupEngine(cfg).fingerprint(data)
    assert [off + ln for off, ln in spans] == reference.cuts_serial(
        data, *widths)
    raw = digests.astype(">u4").tobytes()
    for i, (off, ln) in enumerate(spans):
        chunk = data[off:off + ln]
        assert raw[i * 20:(i + 1) * 20] == hashlib.sha1(chunk).digest(), i
        np.testing.assert_array_equal(
            sigs[i], _np_signature(chunk, cfg.num_perms, cfg.shingle), str(i))


def _rows(n: int, width: int, seed: int, longest: int | None = None):
    rng = np.random.RandomState(seed)
    longest = longest or width
    lens = rng.randint(longest // 2, longest + 1, size=n).astype(np.int32)
    lens[0], lens[-1] = longest, 3      # the longest, and under a shingle
    data = np.zeros((n, width), np.uint8)
    for i in range(n):
        data[i, :lens[i]] = rng.randint(0, 256, lens[i])
    return data, lens


@pytest.mark.parametrize("as_words,longest", [
    (False, None), (True, None), (True, 150 * K + 7)],
    ids=["bytes", "words", "words_longest_row_under_the_width"])
def test_pallas_kernels_at_a_wide_shape_match_hashlib_and_xla(as_words,
                                                              longest):
    """Interpret mode, 8 rows of 256 KiB: the row-major SHA-1 kernel (a
    tile under 128 rows) walks 513 grid steps of 8 blocks, the MinHash
    kernel four steps of 64 KiB a row with the halo between them.  The
    rows go in as bytes, or as the little-endian words the engine's
    staging buffer already is."""
    from fastdfs_tpu.ops.minhash import minhash_batch
    from fastdfs_tpu.ops.pallas_minhash import minhash_batch_pallas
    from fastdfs_tpu.ops.pallas_sha1 import sha1_batch_pallas
    width = 256 * K
    # the third case: no row reaches the tile's width
    data, lens = _rows(8, width, 35, longest)
    arg = data.view(np.uint32) if as_words else data
    got = np.asarray(sha1_batch_pallas(arg, lens, width, sub=1,
                                       interpret=True))
    for i in range(len(lens)):
        assert (got[i].astype(">u4").tobytes()
                == hashlib.sha1(data[i, :lens[i]].tobytes()).digest()), i
    np.testing.assert_array_equal(
        np.asarray(minhash_batch_pallas(arg, lens, interpret=True)),
        np.asarray(minhash_batch(data, lens)))


def test_lane_major_kernel_still_takes_128_rows_and_more():
    """The tiles of 128 rows and more keep the lane-major kernel (the
    shipped widths' full tiles): 130 rows, two lane groups."""
    from fastdfs_tpu.ops.pallas_sha1 import launch_geometry, sha1_batch_pallas
    data, lens = _rows(130, 320, 36)
    got = np.asarray(sha1_batch_pallas(data.view(np.uint32), lens, 320,
                                       sub=1, interpret=True))
    for i in range(len(lens)):
        assert (got[i].astype(">u4").tobytes()
                == hashlib.sha1(data[i, :lens[i]].tobytes()).digest()), i
    assert launch_geometry(130, 320) == (256, 12)
    assert launch_geometry(256, 65536) == (256, 1025)
    assert launch_geometry(32, 65536) == (128, 1032)
    assert launch_geometry(8, 8 * M) == (128, 131080)


# -- the plan --------------------------------------------------------------------

SHIPPED_SHAPES = [(256, 2048), (256, 4096), (256, 8192), (256, 16384),
                  (256, 32768), (32, 32768), (256, 65536), (32, 65536)]
RESTIC_SHAPES = [(128, 512 * K), (16, 512 * K), (64, M), (8, M), (32, 2 * M),
                 (8, 2 * M), (16, 4 * M), (8, 4 * M), (8, 8 * M)]


def test_shipped_widths_plan_the_same_eight_shapes_as_before():
    """16 programs (two kernels a shape): what PR 30 left, pinned."""
    assert plan_shapes(DedupConfig()) == SHIPPED_SHAPES


def test_restic_widths_plan_tiles_under_the_byte_bound():
    cfg = DedupConfig(min_size=RESTIC[0], avg_bits=RESTIC[1],
                      max_size=RESTIC[2])
    assert plan_shapes(cfg) == RESTIC_SHAPES
    assert all(rows * blen <= engine_mod._TILE_MAX_BYTES
               for rows, blen in RESTIC_SHAPES)


def _walked(plan, lens, early_stop=True) -> int:
    """Σ SHA-1 blocks the plan's launches walk: a tile under 128 rows as
    far as its longest chunk, or (the parent's kernel) every tile its
    width."""
    from fastdfs_tpu.ops.pallas_sha1 import launch_geometry
    return sum(launch_geometry(
        rows, blen, max(lens[i] for i in group) if early_stop else None)[1]
        for rows, blen, group in plan)


def _assert_long_chunks_walk_together(plan, lens, cfg):
    """A request that reaches a width whose full tile the byte bound cut
    under 128 rows is planned by length: each tile a run of the chunks in
    order of length, so no chunk is longer than any chunk of a later
    tile."""
    if not engine_mod._serial_width(cfg.row_tile, engine_mod._bucket_len(
            max(lens), cfg.min_size, cfg.max_size)):
        return
    for (_, _, a), (_, _, b) in zip(plan, plan[1:]):
        assert max(lens[i] for i in a) <= min(lens[i] for i in b)


@pytest.mark.parametrize(
    "widths", [(2 * K, 13, 64 * K), NARROW, WIDE, RESTIC, (M, 22, 8 * M)],
    ids=["shipped", "narrow", "wide", "restic", "borg_like"])
def test_tile_plan_never_emits_a_tile_over_the_byte_bound(widths):
    cfg = DedupConfig(min_size=widths[0], avg_bits=widths[1],
                      max_size=widths[2], use_pallas=False, fan_out=1)
    shapes = set(plan_shapes(cfg))
    rng = np.random.RandomState(37)
    requests = [[widths[2]] * n for n in (1, 7, 9, 40, 300)]
    for _ in range(60):         # sparse to dense, every bucket
        n = int(rng.choice([1, 5, 43, 400]))
        requests.append([int(rng.randint(1, widths[2] + 1))
                         for _ in range(n)])
        requests.append([min(widths[2], widths[0] + int(x)) for x in
                         rng.geometric(1.0 / (1 << widths[1]), size=n)])
    for lens in requests:
        plan = tile_plan(lens, cfg.min_size, cfg.max_size, cfg.row_tile)
        assert sorted(i for _, _, g in plan for i in g) == list(
            range(len(lens)))
        for rows, blen, group in plan:
            assert rows * blen <= engine_mod._TILE_MAX_BYTES
            assert (rows, blen) in shapes
            assert 1 <= len(group) <= rows
            assert all(lens[i] <= blen for i in group)
        _assert_long_chunks_walk_together(plan, lens, cfg)


def test_serial_widths_are_those_whose_full_tile_the_byte_bound_cut():
    """The observable the plan switches on: at row_tile 256 a full tile
    has 128 rows and more up to 512 KiB, so no shipped width is one."""
    assert not any(engine_mod._serial_width(256, blen)
                   for _, blen in SHIPPED_SHAPES)
    assert [blen for blen in (512 * K, M, 2 * M, 4 * M, 8 * M)
            if engine_mod._serial_width(256, blen)] == [M, 2 * M, 4 * M, 8 * M]
    # a small row_tile is not the byte bound's cut
    assert not engine_mod._serial_width(64, 64 * K)


@pytest.mark.parametrize("name", sorted(PARENT_PLANS["shipped"]["requests"]))
def test_shipped_widths_plan_each_request_as_the_parent_did(name):
    """PR 30 tuned these plans on the chip and three cells run them: the
    walk's price must not move one tile of them."""
    request = PARENT_PLANS["shipped"]["requests"][name]
    cfg = DedupConfig()
    assert [cfg.min_size, cfg.avg_bits, cfg.max_size] == (
        PARENT_PLANS["shipped"]["widths"])
    plan = tile_plan(request["lengths"], cfg.min_size, cfg.max_size,
                     cfg.row_tile)
    assert [[rows, blen, group] for rows, blen, group in plan] == (
        request["plan"])


@pytest.mark.parametrize("name", sorted(PARENT_PLANS["restic"]["requests"]))
def test_restic_widths_plan_by_length_and_never_walk_more(name):
    request = PARENT_PLANS["restic"]["requests"][name]
    lens, parent = request["lengths"], request["plan"]
    cfg = DedupConfig(min_size=RESTIC[0], avg_bits=RESTIC[1],
                      max_size=RESTIC[2])
    plan = tile_plan(lens, cfg.min_size, cfg.max_size, cfg.row_tile)
    assert sorted(i for _, _, g in plan for i in g) == list(range(len(lens)))
    for rows, blen, group in plan:
        assert (rows, blen) in RESTIC_SHAPES
        assert 1 <= len(group) <= rows          # row 0 is a real chunk
        assert all(lens[i] <= blen for i in group)
    _assert_long_chunks_walk_together(plan, lens, cfg)
    if max(lens) <= 512 * K:    # no serial width reached: the plan before
        assert [[rows, blen, group] for rows, blen, group in plan] == parent
    assert _walked(plan, lens) <= _walked(parent, lens)
    assert len(plan) <= len(parent)


def test_restic_widths_walk_under_six_tenths_of_the_parents_blocks():
    """Over segments of the cell's series (40, 56, 64, 8, 24 MiB; lengths
    512 KiB + geometric(2^20) capped at 8 MiB): against the parent's plan
    under the parent's kernel, which walked every tile's width."""
    cfg = DedupConfig(min_size=RESTIC[0], avg_bits=RESTIC[1],
                      max_size=RESTIC[2])
    walked = width = was = shipped = shipped_was = 0
    for request in PARENT_PLANS["restic"]["requests"].values():
        lens = request["lengths"]
        plan = tile_plan(lens, cfg.min_size, cfg.max_size, cfg.row_tile)
        walked += _walked(plan, lens)
        width += _walked(plan, lens, early_stop=False)
        was += _walked(request["plan"], lens, early_stop=False)
        shipped += sum(rows * blen for rows, blen, _ in plan)
        shipped_was += sum(rows * blen for rows, blen, _ in request["plan"])
    assert walked <= 0.60 * was
    assert shipped <= 1.2 * shipped_was
    # either half alone is not enough: the plan's tiles at their widths
    assert width > 0.60 * was


def test_engine_refuses_widths_no_tile_can_hold():
    with pytest.raises(ValueError, match="max_size"):
        DedupEngine(DedupConfig(min_size=M, avg_bits=22, max_size=64 * M,
                                use_pallas=False))
    with pytest.raises(ValueError, match="min_size"):
        DedupEngine(DedupConfig(min_size=64 * K, avg_bits=13, max_size=2 * K,
                                use_pallas=False))


def test_warmup_compiles_exactly_the_shapes_of_the_widths(monkeypatch):
    seen = []
    monkeypatch.setattr(
        DedupEngine, "_fingerprint_batch",
        lambda self, batch, lens: (
            seen.append(batch.shape) or
            (np.zeros((batch.shape[0], 5), np.uint32),
             np.zeros((batch.shape[0], 64), np.uint32))))
    cfg = DedupConfig(min_size=RESTIC[0], avg_bits=RESTIC[1],
                      max_size=RESTIC[2], use_pallas=False)
    DedupEngine(cfg).warmup()
    assert seen == RESTIC_SHAPES


@pytest.mark.parametrize("tile_bound", [None, 2 * M],
                         ids=["plan_by_bytes", "plan_by_walk"])
def test_dispatch_counts_rows_lanes_and_blocks(monkeypatch, tile_bound):
    """The sums `stats` reports, and the span's arguments, for one request
    at the wide widths: every chunk counted once, 128 lanes a tile under
    128 rows, the blocks each launch walks (under 128 rows as far as its
    longest chunk) and beside them the blocks of each tile's width.  Under
    a tile bound of 2 MiB the same widths are serial ones (8 rows of
    256 KiB fill a tile) and the request takes the walk-aware plan."""
    from fastdfs_tpu.ops.pallas_sha1 import launch_geometry
    if tile_bound:
        monkeypatch.setattr(engine_mod, "_TILE_MAX_BYTES", tile_bound)
    cfg = DedupConfig(min_size=WIDE[0], avg_bits=WIDE[1], max_size=WIDE[2],
                      use_pallas=False)
    assert engine_mod._serial_width(cfg.row_tile, WIDE[2]) == bool(tile_bound)
    eng = DedupEngine(cfg)
    data = _seeded(2 * M, 38)
    spans, digests, _ = eng.fingerprint(data)
    raw = digests.astype(">u4").tobytes()
    for i, (off, ln) in enumerate(spans):
        assert raw[i * 20:(i + 1) * 20] == hashlib.sha1(
            data[off:off + ln]).digest(), i
    lens = [ln for _, ln in spans]
    plan = tile_plan(lens, cfg.min_size, cfg.max_size, cfg.row_tile)
    _assert_long_chunks_walk_together(plan, lens, cfg)
    geo = [launch_geometry(rows, blen) for rows, blen, _ in plan]
    assert eng.launched == {
        "rows_placed": len(spans),
        "lanes_launched": sum(lanes for lanes, _ in geo),
        "sha1_grid_steps": _walked(plan, lens),
        "sha1_width_steps": sum(blocks for _, blocks in geo),
        "pack_rows": len(spans),
        "pack_rows_released": sum(
            len(group) for _, blen, group in plan
            if blen >= engine_mod._RELEASE_ROW_BYTES),
        "pack_copied_bytes": len(data),
        "pack_zeroed_bytes": sum(rows * blen for rows, blen, _ in plan)
        - len(data)}
    assert eng.launched["sha1_grid_steps"] < eng.launched["sha1_width_steps"]


def test_sidecar_answers_widths_and_refuses_foreign_cuts(tmp_path, capsys):
    import struct

    from fastdfs_tpu.sidecar import DedupSidecar, parse_widths
    assert parse_widths("512K:20:8M") == RESTIC
    assert parse_widths("2048:13:65536") == (2 * K, 13, 64 * K)
    cfg = DedupConfig(min_size=NARROW[0], avg_bits=NARROW[1],
                      max_size=NARROW[2], use_pallas=False)
    sc = DedupSidecar(str(tmp_path / "s.sock"), config=cfg)
    assert sc._commit(b"widths %d %d %d" % NARROW) == (0, b"")
    status, why = sc._commit(b"widths 2048 13 65536")
    assert status == 22 and b"2048:13:65536" in why and b"4096:13:65536" in why
    assert "REFUSING a daemon" in capsys.readouterr().out
    # cuts that only another set of widths can have made: one chunk of 96K
    data = _seeded(96 * K, 39)
    body = struct.pack(">qqqq", 7, 0, 1, len(data)) + data
    assert sc._fingerprint(body, with_cuts=True) == (22, b"")
    assert "REFUSING cuts over max_size" in capsys.readouterr().out
    assert sc.stats["fingerprint_bytes"] == 0
    stats = sc.device_info()
    assert stats["widths"] == {"min_size": NARROW[0], "avg_bits": NARROW[1],
                               "max_size": NARROW[2]}
    assert stats["rows_placed"] == stats["lanes_launched"] == 0


def test_snapshot_written_at_other_widths_is_discarded(tmp_path, capsys):
    from fastdfs_tpu.sidecar import DedupSidecar
    state = str(tmp_path / "state")
    os.makedirs(state)

    def sidecar(widths):
        return DedupSidecar(
            os.path.join(state, "s.sock"), state_dir=state,
            config=DedupConfig(min_size=widths[0], avg_bits=widths[1],
                               max_size=widths[2], use_pallas=False))
    first = sidecar(NARROW)
    data = _seeded(300 * K, 40)
    import struct
    assert first._fingerprint(struct.pack(">qq", 1, 0) + data)[0] == 0
    first._commit(b"commitchunks 1 group1/M00/00/00/a.bin")
    first._commit(b"commitfile " + b"ab" * 20 + b" group1/M00/00/00/b.bin")
    first.save_state()
    assert len(first.engine.exact) > 0

    same = sidecar(NARROW)
    assert same.files and len(same.engine.exact) == len(first.engine.exact)
    capsys.readouterr()
    other = sidecar(WIDE)
    assert not other.files and len(other.engine.exact) == 0
    assert len(other.engine.near) == 0
    assert "discarding snapshot built at chunk widths 4096:13:65536" in (
        capsys.readouterr().out)


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """Tracker, a sidecar on --platform cpu at the NARROW widths, and two
    storage daemons behind it: one that states the same widths, one left
    at the shipped ones."""
    base = tmp_path_factory.mktemp("widths")
    sc = Sidecar(str(base / "sc"), ("--platform", "cpu", "--cdc-widths",
                                    _conf_text(NARROW)))
    tr = start_tracker(str(base / "tr"))
    hb = "heart_beat_interval = 1\nstat_report_interval = 1"
    agree = start_storage(
        str(base / "agree"), dedup_mode="sidecar", dedup_sidecar=sc.sock,
        trackers=[f"127.0.0.1:{tr.port}"],
        extra=f"dedup_cdc_widths = {_conf_text(NARROW)}\n{hb}")
    differ = start_storage(
        str(base / "differ"), dedup_mode="sidecar", dedup_sidecar=sc.sock,
        group="group2", trackers=[f"127.0.0.1:{tr.port}"], extra=hb)
    yield sc, agree, differ
    for d in (agree, differ, tr):
        d.stop()
    sc.stop()


def _upload(st, data: bytes) -> str:
    from fastdfs_tpu.client.storage_client import StorageClient
    with StorageClient(st.ip, st.port) as cli:
        fid = cli.upload_buffer(data, ext="bin")
        assert cli.download_to_buffer(fid) == data
    return fid


def _recipe(st, fid: str):
    reader = RecipeReader(st.port)
    try:
        return reader.fetch(fid)
    finally:
        reader.close()


def test_served_path_at_other_widths_stores_the_reference_recipe(cluster):
    """fdfs_storaged + sidecar as an operator starts them, both at
    4K:13:64K: the stored recipe is the reference's (cuts and SHA-1 of
    every chunk), over two segments."""
    sc, agree, _ = cluster
    widths = _widths_dict(NARROW)
    data = _seeded(900 * K, 41) + bytes(70 * K)
    before = sc.stats()
    fid = _upload(agree, data)
    got, logical = _recipe(agree, fid)
    assert logical == len(data)
    assert got == reference.recipe(data, widths)
    after = sc.stats()
    assert after["fingerprint_bytes"] - before["fingerprint_bytes"] == len(data)
    assert after["rows_placed"] - before["rows_placed"] == len(got)
    assert after["widths"] == {"min_size": NARROW[0], "avg_bits": NARROW[1],
                               "max_size": NARROW[2]}
    assert after["sha1_grid_steps"] > before["sha1_grid_steps"]
    assert 0 < after["rows_placed"] <= after["lanes_launched"]


def test_daemon_and_sidecar_at_different_widths_do_not_fingerprint(cluster):
    """The daemon at the shipped widths is refused by the sidecar at
    4K:13:64K when it connects: nothing of its upload is fingerprinted,
    the upload is stored flat (whole, readable), and both logs say why."""
    sc, _, differ = cluster
    data = _seeded(700 * K, 42)
    before = sc.stats()
    fid = _upload(differ, data)
    assert _recipe(differ, fid) is None      # flat: no recipe
    after = sc.stats()
    assert after["fingerprint_bytes"] == before["fingerprint_bytes"]
    assert after["rows_placed"] == before["rows_placed"]
    assert "REFUSED at dedup_cdc_widths 2048:13:65536" in differ.stderr_text
    assert "REFUSING a daemon" in sc.log_text()
    assert "2048:13:65536" in sc.log_text()
