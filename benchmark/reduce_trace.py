"""From a profiler trace to the few numbers the metrics read.

A trace is handled as plain data, ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``, so that
the arithmetic can be checked on a hand-made fixture
(``check_reduce.py``).  :func:`load` makes that shape from the
``.xplane.pb`` the JAX profiler wrote, with
``jax.profiler.ProfileData`` (no backend is initialised).

:func:`reduce` keeps, per device plane, the line that holds the device's
operations (``trace.json`` names both as data):

* ``busy_s``: the union of the intervals in which an operation ran,
  averaged over the device planes;
* ``ops``: seconds by operation (the name up to its `` = ``), summed
  (mean over devices);
* ``modules``: seconds by compiled program on the modules line, its run
  id ``(...)`` taken off the name: what the kernels' rooflines read;
* ``gaps``: the longest idle gaps between operations.
"""

from __future__ import annotations

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def outline(trace: dict, top: int = 12) -> list[dict]:
    """Planes, lines and their heaviest event names: for reading a trace
    by hand before writing a name pattern against it."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            by_name: dict[str, float] = {}
            for name, _, dur in line["events"]:
                by_name[name] = by_name.get(name, 0.0) + dur / 1e9
            heavy = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
            out.append({"plane": plane["name"], "line": line["name"],
                        "events": len(line["events"]), "heaviest": heavy})
    return out


def union_seconds(intervals: list[tuple[int, int]]) -> tuple[float, list]:
    """(seconds covered, merged [(start, end)]) of ns intervals."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged) / 1e9, merged


def reduce(trace: dict, rules: dict | None = None) -> dict | None:
    """None when the trace holds no device plane with operations."""
    if rules is None:
        with open(os.path.join(HERE, "trace.json")) as fh:
            rules = json.load(fh)
    plane_re = re.compile(rules["device_plane_regex"])
    busy, ops, modules, gaps = [], {}, {}, []
    for plane in trace["planes"]:
        if not plane_re.search(plane["name"]):
            continue
        events = [ev for line in plane["lines"]
                  if line["name"] == rules["ops_line"]
                  for ev in line["events"]]
        if not events:
            continue
        covered, merged = union_seconds(
            [(start, start + dur) for _, start, dur in events])
        busy.append(covered)
        for name, _, dur in events:
            name = name.split(" = ", 1)[0]
            ops[name] = ops.get(name, 0.0) + dur / 1e9
        for line in plane["lines"]:
            if line["name"] == rules["modules_line"]:
                for name, _, dur in line["events"]:
                    name = re.sub(r"\(\d+\)$", "", name)
                    modules[name] = modules.get(name, 0.0) + dur / 1e9
        for (_, end), (start, _) in zip(merged, merged[1:]):
            gaps.append((start - end) / 1e9)
    if not busy:
        return None
    n = len(busy)
    return {"devices": n, "busy_s": sum(busy) / n,
            "ops": {k: v / n for k, v in ops.items()},
            "modules": {k: v / n for k, v in modules.items()},
            "gaps": sorted(gaps, reverse=True)[:10]}


def seconds_matching(reduced: dict, pattern: str,
                     among: str = "modules") -> float:
    rx = re.compile(pattern)
    return sum(s for name, s in reduced[among].items() if rx.search(name))
