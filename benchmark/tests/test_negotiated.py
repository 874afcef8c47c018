"""The cell ``source_dedup.negotiated`` rehearsed on the CPU, its six
readers, its operation and its protocol reference:
``python3 -m pytest benchmark/tests/test_negotiated.py``.

The rehearsal drives the cell's own path at tiny sizes: generation 0
stored by a plain upload, every upload of the window through
``FdfsClient.upload_buffer_dedup`` (QUERY_CHUNKING, UPLOAD_RECIPE,
UPLOAD_CHUNKS, the commit's re-index through the sidecar), traced, so the
daemon writes the access log the new readers open.  About 15 s a run on
eight cores; the control and the two planted faults must come out not
correct in this cell too, each caught by the number that is there for it.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import reference  # noqa: E402
import reference_negotiated  # noqa: E402
from layer_metrics import _negotiated  # noqa: E402
from test_correct import rehearse, value  # noqa: E402

CELL = "source_dedup.negotiated"
STAGES = ("negotiate_ms_per_MB", "commit_present_ms_per_MB",
          "commit_verify_ms_per_MB", "commit_reindex_ms_per_MB")
NEW = STAGES + ("wire_bytes_per_logical_byte", "edge_ms_per_MB")


def reader(name: str):
    return importlib.import_module("layer_metrics." + name).read


def test_negotiated_rehearsal_compares_clean_with_every_number_at_its_limit():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    code, line, decided = rehearse(CELL, "--trace", "1")
    assert decided, line["compared"]
    assert code == 1 and line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
    exact = {k: v["value"] for k, v in line["compared"].items()
             if v["rule"] == "max" and k != "not_a_benchmark_run"}
    assert exact and set(exact.values()) == {0}, exact
    assert value(line, "sample_files") >= 1
    assert value(line, "chunk_hit_share") >= 0.5
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got)
    assert all(got[n] > 0 for n in NEW)
    # 2.5 edits a MiB of 1-16 KiB each, cut at 8 KiB: a few percent shipped
    assert 0.01 < got["wire_bytes_per_logical_byte"] < 0.2
    # every metric that lists the cell and is read from the clients' clocks,
    # the program's spans or the new columns is there (the idle shares
    # and the engine's placed bytes need a device beside them)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])
              and (m["source"] in ("program_span", "host_clock")
                   or m["name"] in NEW)
              and not m["name"].startswith("idle_")}
    assert set(NEW) <= listed <= set(got)
    # and none of the readers of command 11 lists it
    assert not any(CELL in m.get("workloads", [])
                   for m in bench["per_layer"]
                   if m["name"].startswith("daemon_"))


@pytest.mark.parametrize("extra,caught_by", [
    (("--control", "failopen"), "fingerprint_bytes_short"),
    (("--fault", "digest"), "recipe_fallbacks"),
    (("--fault", "signature"), "sample_signatures_differ")],
    ids=["failopen", "digest", "signature"])
def test_control_and_planted_faults_are_not_correct_in_the_negotiated_cell(
        extra, caught_by):
    _, line, decided = rehearse(CELL, *extra)
    assert not decided
    assert value(line, caught_by) > 0
    if extra[1] == "digest":
        # the node holds the recipe to its own cut and the chip's SHA-1:
        # every commit is rolled back, every upload falls back to plain,
        # and the operation module counts that as failed
        assert line["failed"] == line["attempted"] > 0


# -- the readers, on a log written by hand ------------------------------------------

ROWS = """\
1 127.0.0.1 11 0 60 900000 500000 400000 300000 0 50000 100 50000015 40000 5 20000 0 0 0 0 0
2 127.0.0.1 149 0 48 30 0 0 0 0 0 0 0 0 0 0 0 0 0 0 0
3 127.0.0.1 132 0 6008 9000 100 8000 0 0 0 0 168031 0 10 0 7000 0 0 0 0
4 127.0.0.1 133 0 60 700000 20000 600000 0 0 250000 90 2400016 0 20 0 0 200000 40000 10000 340000
5 127.0.0.1 132 0 6008 5000 100 4000 0 0 0 0 168031 0 10 0 3000 0 0 0 0
6 127.0.0.1 133 5 0 100000 20000 60000 0 0 0 0 2400016 0 20 0 0 1 1 0 0
{"event":"slow_request"}
7 127.0.0.1 14 0 50000000 90000 0 0 0 0 0 0 80 0 0 0 0 0 0 0 0
"""


def cell_over(tmp_path, text: str | None) -> dict:
    """A cell whose daemon left ``text`` as its access log, and whose
    clients saw one 50 MB negotiated upload acknowledged in 1.2 s."""
    os.makedirs(tmp_path / "sc" / "bench")
    if text is not None:
        os.makedirs(tmp_path / "st" / "logs")
        (tmp_path / "st" / "logs" / "access.log").write_text(text)
    ups = [{"kind": "upload_negotiated", "bytes": 50_000_000, "t_send": 10.0,
            "t_done": 11.2},
           {"kind": "upload", "bytes": 7_000_000, "t_send": 0.0,
            "t_done": 9.0}]
    return {"sidecar": types.SimpleNamespace(
        bench_dir=str(tmp_path / "sc" / "bench")), "uploads": ups}


def test_readers_on_a_small_log(tmp_path):
    cell = cell_over(tmp_path, ROWS)
    rows = _negotiated.rows(cell)
    # acknowledged rows of the two commands only: the refused commit (row
    # 6), the plain upload, the query, the download and the JSON line stay out
    assert [len(rows[132]), len(rows[133])] == [2, 1]
    got = {n: reader(n)(cell) for n in NEW}
    assert got["negotiate_ms_per_MB"] == pytest.approx(10.0 / 50)
    assert got["commit_present_ms_per_MB"] == pytest.approx(200.0 / 50)
    assert got["commit_verify_ms_per_MB"] == pytest.approx(40.0 / 50)
    assert got["commit_reindex_ms_per_MB"] == pytest.approx(340.0 / 50)
    assert got["wire_bytes_per_logical_byte"] == pytest.approx(
        (2 * 168031 + 2400016) / 50e6)
    # 1.2 s on the client's clock, 9 + 5 + 700 ms on the daemon's
    assert got["edge_ms_per_MB"] == pytest.approx((1200.0 - 714.0) / 50)


def test_readers_give_none_where_there_is_nothing_to_read(tmp_path):
    # a daemon from before the columns: sixteen to a row
    old = "\n".join(" ".join(ln.split()[:16]) for ln in ROWS.splitlines()
                    if not ln.startswith("{")) + "\n"
    cell = cell_over(tmp_path / "old", old)
    assert [reader(n)(cell) for n in STAGES] == [None] * 4
    assert reader("wire_bytes_per_logical_byte")(cell) > 0
    assert reader("edge_ms_per_MB")(cell) > 0
    # no access log at all (an untraced run): nothing, and nothing raised
    cell = cell_over(tmp_path / "none", None)
    assert [reader(n)(cell) for n in NEW] == [None] * 6
    # a log, but no negotiated upload was acknowledged
    cell = cell_over(tmp_path / "plain", ROWS.splitlines()[0] + "\n")
    assert [reader(n)(cell) for n in NEW] == [None] * 6


# -- the operation and the generator ------------------------------------------------

def test_operation_turns_a_fallback_into_a_failed_operation():
    op = importlib.import_module("ops.upload_negotiated")
    assert op.STORES is True
    data, known = b"x" * 1000, {}

    class Client:
        def __init__(self, fallback):
            self.fallback = fallback

        def upload_buffer_dedup(self, data, ext="", min_dup_ratio=None,
                                stats=None):
            assert min_dup_ratio == 0 and stats == {}
            stats.update(fallback=self.fallback, bytes_sent=40)
            return "group1/M00/00/00/x.bin"

    size, verdict, fid = op.settle(known, "k", data,
                                   op.send(Client(""), known, "k", data))
    assert (size, verdict, fid) == (1000, "ok", "group1/M00/00/00/x.bin")
    assert known["k"][0] == fid
    size, verdict, fid = op.settle(
        known, "k2", data, op.send(Client("commit_status22"), known, "k2",
                                   data))
    assert verdict == "failed:fell back to plain (commit_status22)"
    assert fid is None and "k2" not in known and size == 1000


def test_generator_is_the_versions_series_under_another_kind():
    params = {"sizes_mib": [0.25, 0.5], "edits_per_mib": 8, "edit_min": 1024,
              "edit_max": 4096}
    old = importlib.import_module("generators.versions").Generator(
        params, 7, 1, 2)
    new = importlib.import_module("generators.versions_negotiated").Generator(
        params, 7, 1, 2)
    assert new.preload() == old.preload()
    kind, key, data = new.next_op()
    assert kind == "upload_negotiated"
    assert ("upload", key, data) == old.next_op()
    assert new.content(key) == data


# -- the protocol's reference --------------------------------------------------------

WIDTHS = {"cdc_min_size": 2048, "cdc_avg_bits": 13, "cdc_max_size": 65536,
          "dedup_segment_bytes": 200000}


def test_protocol_reference_ships_what_the_store_lacks():
    rng = np.random.default_rng(5)
    gen0 = rng.bytes(500_000)
    gen1 = gen0[:120_000] + rng.bytes(3000) + gen0[124_000:]
    recipe, mask, sent = reference_negotiated.exchange([gen0], gen1, WIDTHS)
    assert recipe == reference.recipe(gen1, WIDTHS)
    assert sent == sum(n for (n, _), need in zip(recipe, mask) if need)
    assert 0 < sum(mask) < len(mask) / 2
    # shipped chunks are exactly those no stored file has
    have = {sha for _, sha in reference.recipe(gen0, WIDTHS)}
    assert [int(sha not in have) for _, sha in recipe] == mask
    # every new chunk covers a byte the edit touched or shifted a cut past
    first = next(i for i, need in enumerate(mask) if need)
    assert sum(n for n, _ in recipe[:first + 1]) > 100_000
    # a warm re-upload ships nothing; an empty store takes everything
    assert reference_negotiated.exchange([gen0, gen1], gen1, WIDTHS)[1:] == (
        [0] * len(recipe), 0)
    assert reference_negotiated.exchange([], gen1, WIDTHS)[2] == len(gen1)
