#!/usr/bin/env python
"""Fingerprint-pipeline profile: where does the ingest GB/s go?

Times each stage of the dedup fingerprint path in isolation on the real
device (median of steady-state iters, full device_get fence), so the
headline bench number is explainable instead of guessed at.  Prints one
JSON object per stage, then a final summary object; ``--trace DIR``
additionally captures a JAX profiler trace of the fused pipeline (one
extra ``{"trace_dir": ...}`` line).  One process: it holds the chip for
its whole run, so run it through the chip tool with no sidecar alive.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def fence_median(fn, iters=6):
    import jax
    jax.device_get(fn())  # warm/compile
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.device_get(fn())
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="",
                    help="also capture a JAX profiler trace of one fused "
                         "pipeline round into this directory (open with "
                         "tensorboard/xprof; SURVEY.md §5 tracing)")
    args = ap.parse_args()  # before the heavy jax import: --help stays fast

    import jax

    from fastdfs_tpu.ops.sha1 import sha1_batch
    from fastdfs_tpu.ops.minhash import minhash_batch
    from fastdfs_tpu.ops.pallas_sha1 import sha1_batch_pallas
    from fastdfs_tpu.ops.pallas_minhash import minhash_batch_pallas

    chunk_kb, n_chunks = 64, 2048
    L = chunk_kb * 1024
    total = n_chunks * L
    rng = np.random.RandomState(0)
    chunks = rng.randint(0, 256, size=(n_chunks, L), dtype=np.uint8)
    lens = np.full(n_chunks, L, dtype=np.int32)
    dc, dl = jax.device_put(chunks), jax.device_put(lens)
    jax.block_until_ready((dc, dl))

    results = {}

    def stage(name, fn):
        dt = fence_median(fn)
        results[name] = {"sec": round(dt, 5), "GBps": round(total / dt / 1e9, 3)}
        print(json.dumps({"stage": name, **results[name]}), flush=True)

    # Dispatch floor: a trivial jitted op on the same inputs.
    triv = jax.jit(lambda c: c[0, :8].astype(jnp_u32()))
    stage("dispatch_floor", lambda: triv(dc))

    # Host->device transfer of the whole batch (the streaming cost).
    def h2d():
        a = jax.device_put(chunks)
        a.block_until_ready()
        return a[0, :8]
    stage("host_to_device", h2d)

    stage("sha1_xla", lambda: sha1_batch(dc, dl))
    stage("sha1_pallas", lambda: sha1_batch_pallas(dc, dl, L))
    stage("minhash_xla", lambda: minhash_batch(dc, dl))
    stage("minhash_pallas", lambda: minhash_batch_pallas(dc, dl))

    both = jax.jit(lambda c, ln: (sha1_batch_pallas(c, ln, L),
                                  minhash_batch_pallas(c, ln)))
    stage("fused_pallas_both", lambda: both(dc, dl))

    if args.trace:
        with jax.profiler.trace(args.trace):
            jax.device_get([both(dc, dl) for _ in range(4)])
        print(json.dumps({"trace_dir": args.trace}))

    print(json.dumps({"total_bytes": total, "results": results}))


def jnp_u32():
    import jax.numpy as jnp
    return jnp.uint32


if __name__ == "__main__":
    main()
