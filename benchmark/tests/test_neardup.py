"""The cell ``crawl_neardup.revisit`` rehearsed on the CPU with a small
base, its readers, its operation, its generator and its reference:
``python3 -m pytest benchmark/tests/test_neardup.py``.

The rehearsal drives the cell's own path at tiny sizes (``small_base.py``
cuts ``--near-base`` to 40,000 rows in-process; ``run.py`` is not
edited): set-up strata stored by plain upload, every upload of the window
followed by ``near_dups`` for the file id it was given, traced, so the
readers of the index's spans have something to read.  About 18 s a run.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import reference  # noqa: E402
import reference_neardup  # noqa: E402
from generators import revisits  # noqa: E402
from layer_metrics import _near  # noqa: E402
from ops import near_dups  # noqa: E402

CELL = "crawl_neardup.revisit"
NEW = ("near_scan_roofline", "near_queries_per_scan", "near_scan_ms",
       "near_queue_wait_ms", "near_insert_ms", "near_query_p50_ms",
       "near_query_p99_ms")


def reader(name: str):
    return importlib.import_module("layer_metrics." + name).read


def rehearse(*extra: str) -> tuple[int, dict, bool]:
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "small_base.py"), "40000",
         "--workload", CELL, "--seed", "2147483659", "--seconds", "3",
         "--rehearse", *extra],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.stdout.strip(), proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    decided = [ln for ln in proc.stderr.splitlines()
               if ln.startswith("correct: ")][-1] == "correct: True"
    return proc.returncode, line, decided


def value(line: dict, name: str):
    return line["compared"][name]["value"]


def _traffic() -> dict:
    with open(os.path.join(BENCH, "traffic", "revisit.json")) as fh:
        return json.load(fh)


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "crawl_neardup.json")) as fh:
        return json.load(fh)


# -- the cell, rehearsed -----------------------------------------------------------

def test_neardup_rehearsal_compares_clean_and_reports_its_readers():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    code, line, decided = rehearse("--trace", "1")
    assert decided, line["compared"]
    assert code == 1 and line["correct"] is False
    # an upload, then its query: as many of one as of the other (or one
    # upload more, where the window closed between the two)
    assert line["attempted"] >= 16 and line["failed"] == 0
    exact = {k: v["value"] for k, v in line["compared"].items()
             if v["rule"] == "max" and k != "not_a_benchmark_run"}
    assert exact and set(exact.values()) == {0}, exact
    assert value(line, "sample_files") >= 1
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # every new reader but the roofline's, which needs a device's modules
    assert set(NEW) - {"near_scan_roofline"} <= set(got)
    assert "near_scan_roofline" not in got
    assert got["near_queries_per_scan"] >= 1
    assert got["near_scan_ms"] > 0 and got["near_insert_ms"] > 0
    assert got["near_query_p99_ms"] >= got["near_query_p50_ms"] > 0
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(NEW) <= listed
    # the cell is one chip, one configuration, the traffic the issue names
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "crawl_neardup", "revisit")


@pytest.mark.parametrize("extra,caught_by", [
    (("--control", "failopen"), "eligible_files_stored_flat"),
    (("--fault", "signature"), "sample_signatures_differ")])
def test_neardup_control_and_planted_fault_are_not_correct(extra, caught_by):
    _, line, decided = rehearse(*extra)
    assert not decided
    assert value(line, caught_by) > 0
    if extra[0] == "--control":
        # a node that stored flat has no signature to ask about
        assert line["failed"] > 0


def test_the_full_size_comparison_rehearsed_over_a_small_base():
    """``neardup_fullsize.py`` as it runs on the chip, here on the CPU over
    40,000 base rows: every reply equal to the reference scanning all of
    them, some from families with three stored generations."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "neardup_fullsize.py"),
         "--platform", "cpu", "--base-rows", "40000", "--clients", "1",
         "--strata", "10"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["queries"] == line["equal"] >= 4
    assert line["queries_of_families_with_three_stored_generations"] >= 1
    assert line["base_rows_scanned_by_the_reference"] == 40_000
    assert line["near_rows"] == 40_000 + line["own_rows"]
    assert line["lines_compared"] >= 2


# -- the configuration and the traffic, as the issue sets them out -----------------

def test_the_configuration_cuts_no_width_and_not_the_index():
    cfg, shipped = _config(), None
    with open(os.path.join(BENCH, "configs", "upstream_mix.json")) as fh:
        shipped = json.load(fh)
    assert cfg["widths"] == shipped["widths"]
    assert cfg["storage_conf"] == shipped["storage_conf"]
    near = cfg["near_index"]
    assert near["rows"] == 30_000_000 and near["row_bytes"] == 256
    assert cfg["sidecar_args"] == ["--near-base",
                                   f"{near['rows']}:{near['base_seed']}"]
    assert reference_neardup.scan_bytes(near["rows"],
                                        cfg["widths"]["num_perms"]) == 7.68e9
    for key in ("source_notes", "guarantees", "assumed", "live_rows",
                "document_sizes"):
        assert cfg[key]
    assert "30,000,000" in cfg["source"] and "50%" in cfg["source"]
    t = _traffic()
    assert (t["clients"], t["check_sample"], t["op_timeout_s"]) == (8, 24, 120)
    assert "min_chunk_hit_share" not in t and "rehearse" in t


# -- the generator --------------------------------------------------------------------

def _generator(client: int = 3, seed: int = 2147483659):
    return revisits.Generator(_traffic()["params"], seed, client, 8)


def test_a_stratum_is_six_new_documents_and_their_two_revisits_each():
    gen = _generator()
    for s in (8, 9, 23):
        keys = gen.stratum(s)
        assert len(keys) == 18 and len({tuple(k) for k in keys}) == 18
        by_gen = {g: [k for k in keys if k[3] == g] for g in range(3)}
        assert [len(by_gen[g]) for g in range(3)] == [6, 6, 6]   # 2 of 3
        assert {k[1] for k in by_gen[0]} == {s}
        assert {k[1] for k in by_gen[1]} == {s - 4}
        assert {k[1] for k in by_gen[2]} == {s - 8}
        sizes = sorted(len(gen.content(k)) for k in by_gen[0])
        assert sizes == [204_800] * 5 + [1_048_576]
    assert len(gen.stratum(0)) == 6 and len(gen.stratum(5)) == 12
    # another seed: the same documents in another order
    other = _generator(seed=77)
    assert sorted(map(tuple, other.stratum(9))) == sorted(
        map(tuple, gen.stratum(9)))
    assert other.stratum(9) != gen.stratum(9)


def test_setup_stores_eight_strata_and_the_window_asks_after_every_upload():
    gen = _generator()
    pre = gen.preload()
    assert len(pre) == 4 * 6 + 4 * 12
    assert [k for k, _ in pre[:6]] == gen.stratum(0)
    ops = [gen.next_op() for _ in range(36)]
    assert [k for k, _, _ in ops[0::2]] == ["upload"] * 18
    assert [k for k, _, _ in ops[1::2]] == ["near_dups"] * 18
    assert [key for _, key, _ in ops[0::2]] == gen.stratum(8)
    for (_, up_key, data), (_, q_key, blob) in zip(ops[0::2], ops[1::2]):
        assert q_key == up_key and data == gen.content(up_key)
        assert (blob is not None) == gen.checked(up_key[1], up_key[2])
    # 1 family in 8 is checked, by the seed (not by the generation)
    checked = sum(gen.checked(b, s) for b in range(200) for s in range(6))
    assert 100 < checked < 200
    assert _generator(seed=5).checked(3, 1) == _generator(seed=5).checked(3, 1)


def test_content_is_made_again_from_the_seed_and_a_revisit_differs_little():
    key = [3, 9, 5, 2]
    again = _generator().content(key)
    assert _generator().content(key) == again
    assert _generator(client=4).content([4, 9, 5, 2]) != again
    with pytest.raises(ValueError):
        _generator(client=4).content(key)
    gens = [_generator().content([3, 9, 5, g]) for g in range(3)]
    assert gens[0] != gens[1] != gens[2]
    assert all(abs(len(g) - 1_048_576) < 64_000 for g in gens)
    widths = _config()["widths"]
    sigs = [reference.file_signature(g, widths) for g in gens]
    assert (sigs[0] == sigs[1]).mean() > 0.7 and (sigs[0] == sigs[2]).mean() > 0.5
    # the blob a checked query carries: the generations so far, in order
    head, _, body = _generator()._family_blob(key).partition(b"\n")
    head = json.loads(head)
    assert head["gens"] == [0, 1, 2] and head["config"] == "crawl_neardup"
    assert body == b"".join(gens) and head["sizes"] == [len(g) for g in gens]


# -- the operation's verdicts ---------------------------------------------------------

def _checked_query(generations: int = 3):
    """(known, key of the newest generation, blob, the reference's reply)"""
    gen = _generator()
    birth, slot = next((b, s) for b in range(50) for s in range(5)
                       if gen.checked(b, s))
    known = {}
    for g in range(generations):
        known[json.dumps([3, birth, slot, g])] = (f"group1/M00/00/0{g}/f{g}",
                                                  "-")
    key = json.dumps([3, birth, slot, generations - 1])
    blob = gen._family_blob([3, birth, slot, generations - 1])
    return known, key, blob, near_dups.expected(known, key, blob)


def _reply(lines):
    return [(ref, float(score)) for ref, score in lines]


def test_a_checked_reply_is_held_to_the_reference_line_for_line():
    known, key, blob, want = _checked_query()
    assert sorted(ref for ref, _ in want) == ["group1/M00/00/00/f0",
                                              "group1/M00/00/01/f1"]
    assert near_dups.settle(known, key, blob, _reply(want)) == (0, "ok", None)
    # a wrong line: one score off by a lane
    off = [(want[0][0], f"{float(want[0][1]) - 1 / 64:.4f}")] + want[1:]
    assert near_dups.settle(known, key, blob, _reply(off))[1] == "wrong"
    # a wrong order
    if want[0][1] != want[1][1]:
        assert near_dups.settle(known, key, blob,
                                _reply(want[::-1]))[1] == "wrong"
    swapped = [(want[1][0], want[0][1]), (want[0][0], want[1][1])]
    assert near_dups.settle(known, key, blob, _reply(swapped))[1] == "wrong"
    # a missing generation, an empty reply, a stranger among the lines
    assert near_dups.settle(known, key, blob, _reply(want[:1]))[1] == "wrong"
    assert near_dups.settle(known, key, blob, [])[1] == "wrong"
    assert near_dups.settle(known, key, blob, _reply(
        want + [("base/17", "0.5000")]))[1] == "wrong"
    # the signatures were computed once and kept
    assert sum(k.startswith("sig:") for k in known) == 3


def test_a_generation_whose_upload_failed_is_not_expected():
    known, key, blob, want = _checked_query()
    lost = json.dumps(json.loads(key)[:3] + [1])
    del known[lost]
    fewer = near_dups.expected(known, key, blob)
    assert [ref for ref, _ in fewer] == [r for r, _ in want
                                         if r != "group1/M00/00/01/f1"]
    assert near_dups.settle(known, key, blob, _reply(want))[1] == "wrong"
    assert near_dups.settle(known, key, blob, _reply(fewer))[1] == "ok"


def test_an_unchecked_reply_is_held_to_form():
    known, key, _, want = _checked_query()
    ok = _reply(want)
    assert near_dups.settle(known, key, None, ok) == (0, "ok", None)
    assert near_dups.settle(known, key, None, [])[1] == "ok"
    assert near_dups.settle(known, key, None, ok[::-1])[1] == (
        "ok" if ok[0][1] == ok[1][1] else "wrong")          # not descending
    assert near_dups.settle(known, key, None,
                            ok + [("base/3", 0.5)])[1] == "wrong"   # a stranger
    assert near_dups.settle(known, key, None,
                            [(ok[0][0], 0.4844)])[1] == "wrong"     # under 0.5
    assert near_dups.settle(known, key, None,
                            [(known[key][0], 1.0)])[1] == "wrong"   # itself


# -- the reference ----------------------------------------------------------------------

def test_the_reference_ranks_by_the_rule_over_blocks_of_any_size():
    rng = np.random.default_rng(4)
    rows = rng.integers(0, 1 << 32, (500, 64), dtype=np.uint32)
    query = rows[123].copy()
    rows[400] = query                      # a tie with row 123: older first
    rows[50, :4] = query[:4]               # one band, 4 of 64: under 0.5
    rows[60, :40] = query[:40]             # ten bands: 0.625
    rows[70, 1:33] = query[1:33]           # seven whole bands, 32 lanes: 0.5
    rows[80, 2:34] = query[2:34]           # 32 lanes too, in seven whole bands
    rows[90, ::2] = query[::2]             # 32 lanes, no whole band
    refs = [f"r{i}" for i in range(500)]
    want = [("r123", 1.0), ("r400", 1.0), ("r60", 0.625), ("r70", 0.5),
            ("r80", 0.5)]
    for block in (500, 64, 7):
        sources = [(refs[lo:lo + block], rows[lo:lo + block])
                   for lo in range(0, 500, block)]
        assert reference_neardup.near_dups([query], sources, 16, 0.5, 11) == [
            want]
        assert reference_neardup.near_dups([query], sources, 16, 0.5, 3) == [
            want[:3]]
    # the reply: the asked file dropped, 2 * top_k lines at the most
    assert reference_neardup.reply_lines("r123", want, 5) == [
        ("r400", "1.0000"), ("r60", "0.6250"), ("r70", "0.5000"),
        ("r80", "0.5000")]
    assert reference_neardup.reply_lines("r123", want, 1) == [
        ("r400", "1.0000"), ("r60", "0.6250")]
    empty = np.full(64, reference_neardup.EMPTY, np.uint32)
    assert reference_neardup.near_dups([empty], [(refs, rows)], 16, 0.5, 11) == [[]]
    # the base: refs by rule, rows by their counter
    (name, block), = reference_neardup.base_blocks(3, 100, 64, block=100)
    assert name(7) == "base/7" and block.shape == (100, 64)
    assert np.array_equal(block[7:9], reference_neardup.base_rows(3, 7, 9, 64))
    assert len(np.unique(block)) == block.size


# -- the readers, on a hand-made fixture -------------------------------------------------

def _trace(scans):
    events = [["fdfs.near.scan", 1_000 + 20_000_000 * i, ns, args]
              for i, (ns, args) in enumerate(scans)]
    events.append(["fdfs.engine.dispatch", 5, 100, {"rows": 3}])
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%fusion", 0, 10, {}]]}]},
        {"name": "/host:CPU", "lines": [{"name": "t1", "events": events}]}]}


def test_the_readers_divide_what_the_spans_carry_and_give_none_without():
    scans = [(17_000_000, {"queries": 3, "rows": 30_000_100}),
             (15_000_000, {"queries": 1, "rows": 30_000_104}),
             (16_000_000, {})]                 # a span without arguments
    got = _near.sums(_trace(scans))
    assert got == {"scans": 2, "queries": 4, "rows": 60_000_204,
                   "scan_s": 0.032}
    assert _near.sums(_trace([])) is None
    assert _near.sums({"planes": []}) is None

    cell = {
        "near_sums": got, "config": _config(), "trace_window_s": 30.0,
        "trace": {"modules": {"jit_fdfs_near_scan": 0.0232,
                              "jit_fdfs_near_rank": 0.0018,
                              "jit_sha1_batch_pallas": 0.4}},
        "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
        "device": {"kind": "TPU v5 lite"},
        "host_spans": {"span_s": {"fdfs.near.scan": 0.032,
                                  "fdfs.near.queue_wait": 0.010,
                                  "fdfs.near.insert": 0.0021},
                       "span_n": {"fdfs.near.scan": 2,
                                  "fdfs.near.queue_wait": 4,
                                  "fdfs.near.insert": 3}},
        "ops": [{"kind": "near_dups", "t_send": 0.0, "t_done": 0.02 + i / 1e3,
                 "verdict": "ok"} for i in range(10)]
               + [{"kind": "upload", "t_send": 0.0, "t_done": 9.0,
                   "verdict": "ok"}]}
    bytes_read = 60_000_204 * 256
    assert reader("near_scan_roofline")(cell) == pytest.approx(
        100.0 * (bytes_read / 819e9) / 0.025)
    assert 0 < reader("near_scan_roofline")(cell) <= 100
    assert reader("near_queries_per_scan")(cell) == 2.0
    assert reader("near_scan_ms")(cell) == pytest.approx(16.0)
    assert reader("near_queue_wait_ms")(cell) == pytest.approx(2.5)
    assert reader("near_insert_ms")(cell) == pytest.approx(0.7)
    assert reader("near_query_p50_ms")(cell) == pytest.approx(24.0)
    assert reader("near_query_p99_ms")(cell) == pytest.approx(29.0)

    # a program without the spans (the parent), a run without a trace
    bare = {"near_sums": None, "host_spans": None, "trace": None, "ops": [],
            "config": _config()}
    for name in NEW:
        assert reader(name)(bare) is None
    spanless = dict(cell, near_sums=None,
                    host_spans={"span_s": {}, "span_n": {}})
    for name in NEW[:5]:
        assert reader(name)(spanless) is None
    # the pass's programs not in the trace: no share is made up
    assert reader("near_scan_roofline")(dict(cell, trace={"modules": {
        "jit_sha1_batch_pallas": 0.4}})) is None
