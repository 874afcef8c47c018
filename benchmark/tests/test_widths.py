"""The cell ``restic_chunks.snapshots`` rehearsed on the CPU, and its two
readers: ``python3 -m pytest benchmark/tests/test_widths.py``.

The rehearsal runs at the configuration's real widths (512 KiB / 2^20 /
8 MiB: hashlib and the XLA MinHash on tiles up to the engine's byte
bound) with snapshots of 5 to 9 MiB, traced, so the served path is the
cell's own: ``fdfs_storaged`` at ``dedup_cdc_widths = 512K:20:8M`` and the
sidecar at the same ``--cdc-widths``.  About 25 s on eight cores, and as
much again for each of the two runs broken on purpose (the ``failopen``
control, a planted digest fault) that must come out not correct at these
widths too.
"""

from __future__ import annotations

import copy
import importlib
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from layer_metrics import _dispatch  # noqa: E402
from test_correct import rehearse, value  # noqa: E402

CELL = "restic_chunks.snapshots"
NEW = ("sha1_lane_fill", "sha1_serial_steps_per_MB")


def reader(name: str):
    return importlib.import_module("layer_metrics." + name).read


def test_traced_rehearsal_compares_clean_and_reports_the_new_readers():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    code, line, decided = rehearse(CELL, "--trace", "1")
    assert decided, line["compared"]
    assert code == 1 and line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
    assert value(line, "sample_files") >= 1
    assert value(line, "chunk_hit_share") >= 0.5
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # a snapshot of 5-9 MiB is a handful of chunks on 128 lanes a tile
    assert 0 < got["sha1_lane_fill"] < 0.2
    # 16,384 blocks a MiB, padded to the tile's width: thousands a MB
    assert 1000 < got["sha1_serial_steps_per_MB"] < 100000
    # every metric the cell lists that is read from the program's spans
    # alone (the idle shares need the device's operations beside them)
    spans = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", [CELL])
             and m["source"] == "program_span"
             and not m["name"].startswith("idle_")}
    assert set(NEW) <= spans <= set(got)


@pytest.mark.parametrize("extra,caught_by", [
    (("--control", "failopen"), "eligible_files_stored_flat"),
    (("--fault", "digest"), "sample_recipes_differ")],
    ids=["failopen", "digest"])
def test_control_and_planted_fault_are_not_correct_at_these_widths(
        extra, caught_by):
    _, line, decided = rehearse(CELL, *extra)
    assert not decided
    assert value(line, caught_by) > 0


def _cell_over(trace: dict) -> dict:
    """A cell whose trace is already loaded: what the readers divide."""
    import host_spans
    cell = {"host_spans": host_spans.summarize(trace),
            "dispatch_sums": _dispatch.sums(trace)}
    return cell


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(BENCH, "fixtures", "host_spans_small.json")) as fh:
        return json.load(fh)


def test_readers_give_none_on_a_trace_without_the_arguments(fixture):
    """The fixture is a program from before the arguments (its dispatch
    spans carry none): nothing to read, nothing raised."""
    assert _dispatch.sums(fixture) is None
    cell = _cell_over(fixture)
    assert [reader(n)(cell) for n in NEW] == [None, None]
    # and no trace at all: a run whose sidecar left none behind
    empty = {"sidecar": types.SimpleNamespace(bench_dir=str(HERE))}
    assert [reader(n)(empty) for n in NEW] == [None, None]


def test_readers_sum_the_arguments_of_the_dispatch_spans(fixture):
    trace = copy.deepcopy(fixture)
    launches = iter([{"rows": 32, "lanes": 128, "blen": 2 << 20,
                      "blocks": 32776},
                     {"rows": 11, "lanes": 128, "blen": 4 << 20,
                      "blocks": 65544},
                     {"rows": 200, "lanes": 256, "blen": 65536,
                      "blocks": 1025}])
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                if ev[0] == _dispatch.SPAN:
                    ev[3] = next(launches)
    cell = _cell_over(trace)
    assert cell["dispatch_sums"] == {"rows": 243, "lanes": 512,
                                     "blocks": 99345, "tiles": 3}
    assert reader("sha1_lane_fill")(cell) == pytest.approx(243 / 512)
    mb = cell["host_spans"]["fingerprint_mb"]
    assert reader("sha1_serial_steps_per_MB")(cell) == pytest.approx(
        99345 / mb)
