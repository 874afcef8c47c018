#!/usr/bin/env python3
"""Check the trace reduction on the recorded fixture: the busy union, the
per-name sums, the idle gaps and the two kernels' seconds as
the roofline readers take them.  Needs no chip and no JAX.

    python3 benchmark/check_reduce.py
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import reduce_trace  # noqa: E402


def close(a: float, b: float) -> bool:
    return abs(a - b) < 1e-9


def main() -> int:
    with open(os.path.join(HERE, "fixtures", "trace_small.json")) as fh:
        fixture = json.load(fh)
    want = fixture["expect"]
    got = reduce_trace.reduce(fixture)
    bad = []
    for key in ("devices", "busy_s"):
        if not close(got[key], want[key]):
            bad.append(f"{key}: {got[key]} != {want[key]}")
    if set(got["ops"]) != set(want["ops"]) or not all(
            close(got["ops"][k], v) for k, v in want["ops"].items()):
        bad.append(f"ops: {got['ops']} != {want['ops']}")
    if got["modules"].keys() != want["modules"].keys() or not all(
            close(got["modules"][k], v) for k, v in want["modules"].items()):
        bad.append(f"modules: {got['modules']} != {want['modules']}")
    if len(got["gaps"]) != len(want["gaps"]) or not all(
            close(a, b) for a, b in zip(got["gaps"], want["gaps"])):
        bad.append(f"gaps: {got['gaps']} != {want['gaps']}")
    for metric, key in (("sha1_roofline", "sha1_s"),
                        ("minhash_roofline", "minhash_s")):
        with open(os.path.join(HERE, "layer_metrics", metric + ".json")) as fh:
            pattern = json.load(fh)["event_name_regex"]
        seconds = reduce_trace.seconds_matching(got, pattern)
        if not close(seconds, want[key]):
            bad.append(f"{metric} pattern {pattern!r}: {seconds} != {want[key]}")
    if reduce_trace.reduce({"planes": fixture["planes"][1:]}) is not None:
        bad.append("a trace with no device plane must reduce to None")
    for line in bad:
        print("FAIL", line)
    print("ok" if not bad else f"{len(bad)} check(s) failed")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
