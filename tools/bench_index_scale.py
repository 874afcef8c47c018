#!/usr/bin/env python
"""Scale proof for the columnar ExactDigestIndex (and the LSH ref map).

The index docstring claims ~36 B/entry and "engineered for tens of
millions of entries"; this harness turns the claim into a measured
artifact: RAM per entry, insert + lookup rates, merge pauses, snapshot
size and save/load time at N synthetic chunks (default 10M — config 5's
nominal corpus is ~62M chunks across 4 nodes, so 10M+ is one node's
realistic steady state).  Pure-index run, no daemon needed.

Run:  python tools/bench_index_scale.py [--n 10000000] [--out FILE]
Writes bench_artifacts/index_scale.json by default.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def rss_mb() -> float:
    with open("/proc/self/status") as fh:
        for line in fh:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=10_000_000)
    ap.add_argument("--out", default=os.path.join(
        REPO, "bench_artifacts", "index_scale.json"))
    args = ap.parse_args()

    from fastdfs_tpu.dedup.index import ExactDigestIndex

    n = args.n
    rng = np.random.RandomState(42)
    # Synthetic 20-byte digests (uniform random — the same key
    # distribution real SHA1 output has).  Generated in one array so the
    # generator's cost and RAM stay out of the index measurements.
    digs = rng.randint(0, 256, size=(n, 20), dtype=np.uint8)
    keys = digs.view("S20").ravel()

    idx = ExactDigestIndex()
    rss0 = rss_mb()

    # -- inserts as the sidecar's commits make them: one batch a file of
    # 1,280 digests (10 MiB at 8 KB chunks), every digest new, carriers
    # cycling over 1000 file ids ------------------------------------------
    t0 = time.perf_counter()
    max_pause = 0.0
    batch, commit = 100_000, 1280
    for start in range(0, n, batch):
        t_b = time.perf_counter()
        end = min(start + batch, n)
        for c in range(start, end, commit):
            e = min(c + commit, end)
            idx.insert_batch(digs[c:e].tobytes(), f"f{(c // commit) % 1000}",
                             np.arange(c, e))
        max_pause = max(max_pause, time.perf_counter() - t_b)
    insert_s = time.perf_counter() - t0
    rss_after_insert = rss_mb()

    # -- batched lookups (the engine's judge path) -------------------------
    m = 1_000_000
    probe_hit = [bytes(keys[i]) for i in
                 rng.randint(0, n, m // 2)]
    probe_miss = [bytes(rng.randint(0, 256, 20, dtype=np.uint8))
                  for _ in range(1000)]
    t0 = time.perf_counter()
    got = idx.lookup_batch(probe_hit)
    lookup_batch_s = time.perf_counter() - t0
    assert all(r is not None for r in got)
    t0 = time.perf_counter()
    for d in probe_miss:
        idx.lookup(d)
    lookup_scalar_s = time.perf_counter() - t0

    # -- removals + merge compaction --------------------------------------
    t0 = time.perf_counter()
    for i in range(0, n, 1000):
        idx.remove(bytes(keys[i]))
    remove_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx._merge()
    merge_s = time.perf_counter() - t0

    # -- snapshot ----------------------------------------------------------
    with tempfile.TemporaryDirectory() as tmp:
        p = os.path.join(tmp, "exact")
        t0 = time.perf_counter()
        idx.save(p)
        save_s = time.perf_counter() - t0
        size_mb = os.path.getsize(p + ".npz") / 1e6
        t0 = time.perf_counter()
        idx2 = ExactDigestIndex.load(p)
        load_s = time.perf_counter() - t0
        assert len(idx2) == len(idx)

    out = {
        "entries": n,
        "insert_seconds": round(insert_s, 2),
        "inserts_per_sec": round(n / insert_s),
        "max_100k_batch_pause_s": round(max_pause, 3),
        "rss_before_mb": round(rss0, 1),
        "rss_after_insert_mb": round(rss_after_insert, 1),
        "index_bytes_per_entry": round(
            (rss_after_insert - rss0) * 1e6 / n, 1),
        "lookup_batch_per_sec": round(len(probe_hit) / lookup_batch_s),
        "lookup_scalar_per_sec": round(len(probe_miss) / lookup_scalar_s),
        "remove_per_sec": round((n // 1000) / remove_s),
        "final_merge_seconds": round(merge_s, 3),
        "snapshot_mb": round(size_mb, 1),
        "snapshot_save_seconds": round(save_s, 2),
        "snapshot_load_seconds": round(load_s, 2),
        "note": "synthetic uniform 20B digests inserted as commits of "
                "1,280 (one insert_batch a file); carriers interned over "
                "1000 file ids; rss delta includes the generator-side "
                "probe lists",
    }
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
