#!/usr/bin/env python
"""What packing a tile costs a request when other threads are in Python.

The engine packs each tile of a fingerprint request into a staging
buffer (``engine._pack_tile``).  This script packs the same tile plans
on the host's CPU alone (no JAX call, no device), by:

* ``per_row_numpy`` — the loop the engine had until the pack became
  ``_pack_tile``: the whole tile zeroed, then one numpy slice copy a
  chunk.  A numpy copy of more than 500 elements lets the interpreter
  go, so a tile is as many hand-offs as it has rows;
* ``pack_tile`` — ``engine._pack_tile``: a memmove a row that keeps the
  interpreter, tails zeroed from a shared zero buffer, the empty rows in
  one call; a tile ``_RELEASE_ROW_BYTES`` wide or wider zeroed past its
  shortest chunk in one numpy call and its rows copied by numpy;
* ``memmove_every_row`` — ``_pack_tile`` with no tile packed by numpy
  (restic's widths only: at the shipped ones it is ``pack_tile``).

Each is run by 1, 4 and 20 threads that pack requests back to back, with
and without 2 more threads that spin in Python (as a server's other
threads would: parsing, replying), at the shipped chunk widths (10 MiB
objects cut 2K/8K/64K, what an object PUT sends) and at restic's
(64 MiB segments cut 512K/1M/8M, 4 threads).  Reported per setting:
a request's pack time per MB (median and 90th percentile over the
requests), the packing threads' CPU per MB, and the MB/s packed by all
threads together.  Every method is checked to write the same bytes into
a buffer that held other bytes before.

Run:  python tools/bench_pack_convoy.py [--seconds 3] [--out FILE]
Writes bench_artifacts/pack_convoy.json by default (about two minutes).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import platform
import statistics
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from fastdfs_tpu.dedup import engine  # noqa: E402
from fastdfs_tpu.ops import gear_cdc  # noqa: E402

K, M = 1 << 10, 1 << 20
SHIPPED = (2 * K, 13, 64 * K)
RESTIC = (512 * K, 20, 8 * M)


def per_row_numpy(buf, src, spans, group, rows, blen):
    """The engine's pack before ``_pack_tile``, as it was."""
    tile = buf.reshape(rows, blen)
    arr = np.frombuffer(src, dtype=np.uint8)
    tile[:] = 0
    lens = np.zeros(rows, dtype=np.int32)
    for row, i in enumerate(group):
        off, ln = spans[i]
        tile[row, :ln] = arr[off:off + ln]
        lens[row] = ln
    return lens


def pack_tile(buf, src, spans, group, rows, blen):
    return engine._pack_tile(buf, src, spans, group, rows, blen)[0]


# name -> (pack, the _RELEASE_ROW_BYTES it runs under; None: the engine's)
METHODS = {"per_row_numpy": (per_row_numpy, None),
           "pack_tile": (pack_tile, None),
           # no tile wide enough to be packed by numpy: what the bound is
           # measured against, at restic's widths (at the shipped ones it
           # is pack_tile)
           "memmove_every_row": (pack_tile, 1 << 62)}


@contextlib.contextmanager
def release_bound(bound):
    kept = engine._RELEASE_ROW_BYTES
    engine._RELEASE_ROW_BYTES = kept if bound is None else bound
    try:
        yield
    finally:
        engine._RELEASE_ROW_BYTES = kept


def request(widths, nbytes: int, seed: int):
    """A seeded body, its gear-CDC spans and the engine's tile plan."""
    data = np.random.default_rng(seed).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()
    cuts = gear_cdc.chunk_stream_np(data, *widths)
    spans, last = [], 0
    for c in cuts:
        spans.append((last, c - last))
        last = c
    plan = engine.tile_plan([ln for _, ln in spans], widths[0], widths[2],
                            256)
    return memoryview(np.frombuffer(data, dtype=np.uint8)), spans, plan


def same_layout(src, spans, plan) -> bool:
    """Every method writes the same bytes and lens into a dirty buffer."""
    for rows, blen, group in plan:
        out = set()
        for pack, bound in METHODS.values():
            buf = np.full(rows * blen, 0xAB, dtype=np.uint8)
            with release_bound(bound):
                lens = pack(buf, src, spans, group, rows, blen)
            out.add((buf.tobytes(), tuple(lens.tolist())))
        if len(out) != 1:
            return False
    return True


def spin(stop: threading.Event) -> None:
    n = 0
    while not stop.is_set():
        n += 1


def measure(pack, src, spans, plan, threads: int, spinners: int,
            seconds: float) -> dict:
    mb = len(src) / 1e6
    stop = threading.Event()
    start = threading.Barrier(threads + spinners + 1)
    times: list[float] = []
    cpu: list[float] = []
    lock = threading.Lock()

    def worker():
        bufs = {}
        mine, c0 = [], 0.0
        start.wait()
        c0 = time.thread_time()
        while not stop.is_set():
            t0 = time.perf_counter()
            for rows, blen, group in plan:
                buf = bufs.get(rows * blen)
                if buf is None:
                    buf = bufs[rows * blen] = np.zeros(rows * blen, np.uint8)
                pack(buf, src, spans, group, rows, blen)
            mine.append(time.perf_counter() - t0)
        with lock:
            times.extend(mine)
            cpu.append(time.thread_time() - c0)

    def spinner():
        start.wait()
        spin(stop)

    pool = ([threading.Thread(target=worker) for _ in range(threads)]
            + [threading.Thread(target=spinner) for _ in range(spinners)])
    for t in pool:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    time.sleep(seconds)
    stop.set()
    for t in pool:
        t.join()
    wall = time.perf_counter() - t0
    q = statistics.quantiles(times, n=10) if len(times) > 1 else times * 9
    return {"threads": threads, "spinners": spinners,
            "requests": len(times),
            "pack_ms_per_MB_p50": statistics.median(times) * 1e3 / mb,
            "pack_ms_per_MB_p90": q[8] * 1e3 / mb,
            "cpu_ms_per_MB": sum(cpu) * 1e3 / (len(times) * mb),
            "aggregate_MBps": len(times) * mb / wall}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=os.path.join(
        REPO, "bench_artifacts", "pack_convoy.json"))
    args = ap.parse_args()

    settings = [("shipped_10MiB", SHIPPED, 10 * M, (1, 4, 20),
                 ("per_row_numpy", "pack_tile")),
                ("restic_64MiB", RESTIC, 64 * M, (4,), tuple(METHODS))]
    report = {"host": {"cpus": os.cpu_count(),
                       "python": platform.python_version(),
                       "numpy": np.__version__,
                       "switch_interval_s": sys.getswitchinterval()},
              "seconds_a_setting": args.seconds, "cases": []}
    for name, widths, nbytes, thread_counts, methods in settings:
        src, spans, plan = request(widths, nbytes, seed=43)
        case = {"name": name, "widths": list(widths), "bytes": nbytes,
                "chunks": len(spans), "tiles": [[r, b, len(g)]
                                                for r, b, g in plan],
                "same_layout": same_layout(src, spans, plan), "runs": []}
        for threads in thread_counts:
            for spinners in (0, 2):
                for method in methods:
                    pack, bound = METHODS[method]
                    with release_bound(bound):
                        row = {"method": method, **measure(
                            pack, src, spans, plan, threads, spinners,
                            args.seconds)}
                    case["runs"].append(row)
                    print(f"{name} {method:17s} threads {threads:2d} "
                          f"spinners {spinners}: "
                          f"{row['pack_ms_per_MB_p50']:8.2f} ms/MB p50, "
                          f"{row['pack_ms_per_MB_p90']:8.2f} p90, cpu "
                          f"{row['cpu_ms_per_MB']:.2f}, "
                          f"{row['aggregate_MBps']:8.1f} MB/s", flush=True)
        report["cases"].append(case)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(report, fh, indent=1)
        fh.write("\n")
    print(f"wrote {args.out}")
    return 0 if all(c["same_layout"] for c in report["cases"]) else 1


if __name__ == "__main__":
    sys.exit(main())
