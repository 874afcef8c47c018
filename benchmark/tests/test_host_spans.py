"""``host_spans.py`` on a hand-made trace with two request threads: the idle
classification, the gaps by name, the per-MB sums the readers divide, and
what a program without spans (the parent of the PR that added them) gives.
Needs no chip and no JAX: ``python3 -m pytest benchmark/tests/test_host_spans.py``.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import host_spans  # noqa: E402


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(BENCH, "fixtures", "host_spans_small.json")) as fh:
        return json.load(fh)


def reader(name: str):
    return importlib.import_module("layer_metrics." + name).read


def test_idle_time_is_put_down_to_what_the_host_did(fixture):
    got, want = host_spans.summarize(fixture), fixture["expect"]
    assert got["idle_s"] == pytest.approx(want["idle_s"])
    # the three states are all of the idle time: extent 6.4 s, busy 1.1 s
    assert sum(got["idle_s"].values()) == pytest.approx(6.4 - 1.1)
    assert got["fingerprint_mb"] == want["fingerprint_mb"]
    assert got["requests"] == want["requests"]
    assert got["stall_s"] == pytest.approx(want["stall_s"])
    # recv and send of the commit request are not the fingerprint RPC's
    assert got["span_s"]["fdfs.sidecar.recv"] == pytest.approx(want["recv_s"])
    assert got["span_s"]["fdfs.sidecar.send"] == pytest.approx(want["send_s"])
    # one pack a tile (1 + 2), one recv a fingerprint request
    assert (got["span_n"]["fdfs.engine.pack"],
            got["span_n"]["fdfs.sidecar.recv"]) == (3, 2)


def test_gaps_are_named_by_state_and_innermost_span(fixture):
    got = host_spans.name_gaps(fixture)
    assert len(got) == len(fixture["expect"]["gaps"])
    for row, want in zip(got, fixture["expect"]["gaps"]):
        want = dict(want)
        shares = want.pop("shares")
        assert row["shares"] == pytest.approx(shares, abs=1e-12)
        assert {k: row.get(k) for k in want} == pytest.approx(want)
    assert len(host_spans.name_gaps(fixture, n=2)) == 2


def test_the_readers_divide_by_fingerprinted_mb_and_by_the_window(fixture):
    want = fixture["expect"]
    cell = {"host_spans": host_spans.summarize(fixture),
            "trace_window_s": 6.4}
    assert reader("engine_pack_ms_per_MB")(cell) == pytest.approx(
        want["pack_scatter_s"] * 1e3 / want["fingerprint_mb"])
    assert reader("engine_wait_ms_per_MB")(cell) == pytest.approx(
        want["wait_s"] * 1e3 / want["fingerprint_mb"])
    assert reader("rpc_recv_ms_per_MB")(cell) == pytest.approx(
        want["recv_s"] * 1e3 / want["fingerprint_mb"])
    assert reader("sidecar_stall_ms_per_MB")(cell) == pytest.approx(70.0)
    shares = [reader(f"idle_{s}_pct")(cell) for s in host_spans.STATES]
    assert shares == pytest.approx(
        [100 * want["idle_s"][s] / 6.4 for s in host_spans.STATES])
    assert sum(shares) == pytest.approx(100 * (1 - 1.1 / 6.4))
    # a window longer than the trace's extent: its ends are no_request's
    cell["trace_window_s"] = 6.9
    assert cell["host_spans"]["extent_s"] == pytest.approx(6.4)
    assert reader("idle_no_request_pct")(cell) == pytest.approx(
        100 * (want["idle_s"]["no_request"] + 0.5) / 6.9)
    assert sum(reader(f"idle_{s}_pct")(cell) for s in host_spans.STATES) \
        == pytest.approx(100 * (1 - 1.1 / 6.9))


def test_clock_check_and_program_shares(fixture):
    want = fixture["expect"]
    assert list(host_spans.fetch_holds_device_end(fixture)) == \
        want["fetch_holding_device_end"]
    programs = host_spans.programs(fixture)
    assert {k: v["s"] for k, v in programs.items()} == pytest.approx(
        want["programs"])
    assert "%concatenate.3" in programs[want["concat_owner"]]["ops"]


def test_a_program_without_spans_reads_as_nothing(fixture, tmp_path):
    bare = {"planes": [fixture["planes"][0],
                       {"name": "/host:CPU", "lines": [
                           {"name": "python", "events": [
                               ["$some.runtime.event", 0, 5, {}]]}]}]}
    assert host_spans.summarize(bare) is None
    assert host_spans.name_gaps(bare) == []
    # a run whose trace and log come from such a program: every reader of
    # this file's metrics leaves its metric out, and none raises
    run_dir = tmp_path / "_run"
    os.makedirs(run_dir / "st" / "logs")
    os.makedirs(run_dir / "sc" / "bench")
    (run_dir / "st" / "logs" / "access.log").write_text(
        "1 127.0.0.1 11 0 9 50 10 30 20 1 5 1 4000\n" * 3)
    cell = {"sidecar": types.SimpleNamespace(
        bench_dir=str(run_dir / "sc" / "bench")),
        "preloaded_files": 1, "trace_window_s": 6.4}
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as fh:
        names = [m["name"] for m in json.load(fh)["per_layer"]]
    first = names.index("daemon_cdc_ms_per_MB")     # the twelve of PR 26
    mine = names[first:first + 12]
    assert mine[-1] == "idle_sidecar_pct"
    assert [reader(n)(cell) for n in mine] == [None] * 12


def test_late_columns_skip_the_preload_and_failed_uploads(tmp_path):
    run_dir = tmp_path / "_run"
    os.makedirs(run_dir / "st" / "logs")
    row = "1 127.0.0.1 {cmd} {status} 9 900 10 800 400 1 5 1 {req} {late}\n"
    (run_dir / "st" / "logs" / "access.log").write_text(
        row.format(cmd=11, status=0, req=1000000, late="70 30 90")    # preload
        + '{"event":"slow_request"}\n'
        + row.format(cmd=14, status=0, req=50, late="0 9 0")          # download
        + row.format(cmd=11, status=0, req=2000000, late="100 20 300")
        + row.format(cmd=11, status=16, req=500000, late="5 5 5")     # refused
        + row.format(cmd=11, status=0, req=3000000, late="150 40 200"))
    cell = {"sidecar": types.SimpleNamespace(
        bench_dir=str(run_dir / "sc" / "bench")), "preloaded_files": 1}
    assert host_spans.late_columns(cell) == [
        {"req_bytes": 2000000, "cdc_us": 100, "dio_wait_us": 20,
         "readback_us": 300},
        {"req_bytes": 3000000, "cdc_us": 150, "dio_wait_us": 40,
         "readback_us": 200}]
    assert reader("daemon_cdc_ms_per_MB")(cell) == pytest.approx(0.25 / 5)
    assert reader("daemon_dio_wait_ms_per_MB")(cell) == pytest.approx(0.06 / 5)
    assert reader("daemon_readback_ms_per_MB")(cell) == pytest.approx(0.5 / 5)
