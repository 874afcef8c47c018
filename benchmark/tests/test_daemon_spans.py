"""``daemon_spans.py`` on a hand-made trace and hand-made stage lines: two
uploads that overlap, a download, a negotiation, a file under the chunk
threshold, one before the trace and one across its end.  The five shares are
known, obey the order with overlapping uploads, sum to what
``idle_no_request_pct`` reads (the window's ends included), and the clock
match reads 100 with the right offset and less with a planted skew.
Needs no chip and no JAX: ``python3 -m pytest benchmark/tests/test_daemon_spans.py``.
"""

from __future__ import annotations

import copy
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import daemon_spans  # noqa: E402
import host_spans  # noqa: E402

NAMES = {"rpc": "idle_daemon_rpc_pct", "prepare": "idle_daemon_prepare_pct",
         "recv": "idle_daemon_recv_pct", "store": "idle_daemon_store_pct",
         "no_upload": "idle_no_upload_pct"}


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(BENCH, "fixtures", "daemon_spans_small.json")) as fh:
        return json.load(fh)


def reader(name: str):
    return importlib.import_module("layer_metrics." + name).read


def last_done_mono_s(fixture) -> float:
    want = fixture["expect"]
    return (want["last_done_trace_s"] * 1e9 - want["offset_ns"]) / 1e9


def test_the_markers_give_the_offset_between_the_two_clocks(fixture):
    got, want = daemon_spans.anchors(fixture), fixture["expect"]
    assert got == {k: want[k] for k in ("offset_ns", "spread_ns", "anchors")}
    # a trace from before the markers carried mono_us has no anchor
    old = copy.deepcopy(fixture)
    for plane in old["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                ev[3].pop("mono_us", None)
    assert daemon_spans.anchors(old) is None
    assert daemon_spans.summarize(old, fixture["stage_lines"]) is None
    assert daemon_spans.summarize(fixture, []) is None


def test_idle_time_is_put_down_to_the_upload_furthest_along(fixture):
    got = daemon_spans.summarize(fixture, fixture["stage_lines"])
    want = fixture["expect"]
    assert got["idle_s"] == pytest.approx(want["idle_s"], abs=1e-4)
    assert got["uploads"] == 6 and got["swept_s"] == pytest.approx(10.0)
    # the five are what host_spans calls no_request, no more and no less
    assert sum(got["idle_s"].values()) == pytest.approx(
        host_spans.summarize(fixture)["idle_s"]["no_request"])
    assert sum(got["idle_s"].values()) == pytest.approx(want["no_request_s"])


def test_the_readers_sum_to_idle_no_request_pct_with_the_windows_ends(fixture):
    want = fixture["expect"]
    cell = {"host_spans": host_spans.summarize(fixture),
            "daemon_spans": daemon_spans.summarize(
                fixture, fixture["stage_lines"], want["window_s"],
                last_done_mono_s(fixture)),
            "trace_window_s": want["window_s"]}
    shares = {s: reader(n)(cell) for s, n in NAMES.items()}
    total = {s: want["idle_s"][s] + want["ends_s"].get(s, 0.0)
             for s in NAMES}
    assert shares == pytest.approx(
        {s: 100 * v / want["window_s"] for s, v in total.items()}, abs=1e-3)
    assert sum(shares.values()) == pytest.approx(
        reader("idle_no_request_pct")(cell))
    # no completion time known: the whole of the ends lies after the trace
    behind = daemon_spans.summarize(fixture, fixture["stage_lines"],
                                    want["window_s"])
    assert sum(behind["idle_s"].values()) == pytest.approx(
        want["no_request_s"] + 1.0)
    assert behind["idle_s"]["store"] == pytest.approx(want["idle_s"]["store"],
                                                      abs=1e-4)
    assert reader("clock_match_daemon_pct")(cell) == 100.0


def test_clock_match_falls_with_a_planted_skew(fixture):
    got = daemon_spans.summarize(fixture, fixture["stage_lines"])
    assert (got["rpc_spans_matched"], got["rpc_spans"]) == (2, 2)
    assert got["tolerance_ns"] == 4000 and got["trace_clock"] == "session"
    want = fixture["expect"]
    skewed = copy.deepcopy(fixture)
    for plane in skewed["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                if "mono_us" in ev[3]:
                    ev[3]["mono_us"] += want["skew_us"]
    got = daemon_spans.summarize(skewed, fixture["stage_lines"])
    assert got["offset_ns"] == want["offset_ns"] - want["skew_us"] * 1000
    assert [got["rpc_spans_matched"], got["rpc_spans"]] == want["skewed_match"]
    cell = {"daemon_spans": got, "trace_window_s": 10.0}
    assert reader("clock_match_daemon_pct")(cell) == 50.0
    # another session under the same base offset is another upload's RPC
    other = copy.deepcopy(fixture["stage_lines"])
    other[1]["spans"][6][4]["session"] = 76
    got = daemon_spans.summarize(fixture, other)
    assert (got["rpc_spans_matched"], got["rpc_spans"]) == (1, 2)


def test_gaps_name_the_daemon_state_and_span(fixture):
    rows = daemon_spans.name_gaps(fixture, fixture["stage_lines"])
    (row,), want = rows, fixture["expect"]["gap"]
    assert row["gap_s"] == pytest.approx(want["gap_s"])
    assert row["daemon_shares"] == pytest.approx(want["daemon_shares"],
                                                 abs=1e-5)
    # what the sidecar's states leave of the gap is the daemon's to name
    assert sum(row["daemon_shares"].values()) == pytest.approx(
        row["shares"]["no_request"], abs=1e-5)
    assert row["daemon_span"] == want["daemon_span"]
    assert row["daemon_span_share"] == pytest.approx(
        want["daemon_span_share"], abs=1e-5)
    # without stage lines: host_spans' rows as they are
    assert daemon_spans.name_gaps(fixture, []) == host_spans.name_gaps(fixture)


def test_a_run_without_stage_lines_or_anchors_leaves_the_metrics_out():
    cell = {"daemon_spans": None, "trace_window_s": 30.0}
    for name in list(NAMES.values()) + ["clock_match_daemon_pct"]:
        assert reader(name)(cell) is None
