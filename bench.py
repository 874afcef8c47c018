#!/usr/bin/env python
"""Headline benchmark: dedup-ingest fingerprint throughput, GB/s per chip.

Measures the TPU upload-path fingerprint pipeline — the fused Pallas
SHA1 + MinHash survivor-sketch kernels over chunk batches, the compute
that replaces the reference's scalar CRC32 loop in
``storage/storage_dio.c:dio_write_file()`` — in steady state, and
compares against the single-core CPU baseline (hashlib SHA1, the
reference-style scalar path) on identical data.

This is the bare kernel loop, not the served path (tracker + storaged +
sidecar): ``chip_smoke.py`` drives that one.  One process; it holds the
chip for its whole run, so no sidecar may be alive beside it.

Methodology: 512 MB batches with a depth-``PIPELINE`` dispatch pipeline,
fenced once per round by the ``device_get`` of every batch's
digests+signatures — digests must return to the host to drive the dedup
index, so that is also the realistic cost boundary.  The bench runs at
least MIN_ROUNDS rounds and keeps going until it has measured
MIN_SECONDS of steady state (up to MAX_ROUNDS), reports the FULL
distribution (min / median / max / relative IQR), and flags
``contended = (max-min)/median > 0.30`` so a capture that straddled a
noisy episode says so in the artifact.  The headline value is the median
round; under contention the median of the upper half is also reported
(``value_uncontended``).  No number from this file has been taken on the
v5e machine yet (PERF.md).

Prints ONE JSON line:
  {"metric": "dedup_ingest_GBps_per_chip", "value": N, "unit": "GB/s",
   "ok": true, "device": {"platform": "tpu", "kind": ..., "count": N},
   "vs_baseline": N, "dispersion": {...}, "contended": bool, ...}
and exits 0.  A run that finds no chip prints ``"ok": false, "value":
null`` with the device it did find and exits 1: a CPU rate is never
written under the chip metric's name.

``bench.py --multichip`` runs the fan-out leg instead: the
``parallel.make_fingerprint_step`` shard_map over 1 device and over all
local devices, emitting per-chip AND aggregate GB/s plus the 1->N
scaling ratio (metric ``dedup_ingest_GBps_multichip``).

``_FDFS_BENCH_SMOKE=1`` shrinks every leg to seconds and lets it run
without a chip (Pallas in interpret mode), so the tests can rehearse the
code path and the artifact contract on the CPU.  The verdict is the
same: no chip, ``ok: false``, ``value: null``, exit 1, and the rates the
rehearsal saw are not printed.
"""

import hashlib
import json
import os
import sys
import time

import numpy as np

_SMOKE = os.environ.get("_FDFS_BENCH_SMOKE") == "1"

CHUNK_KB = 2 if _SMOKE else 64
N_CHUNKS = 32 if _SMOKE else 8192      # 512 MB per dispatch (full size)
PIPELINE = 2 if _SMOKE else 8
MIN_ROUNDS = 2 if _SMOKE else 7
MAX_ROUNDS = 3 if _SMOKE else 15
MIN_SECONDS = 0.0 if _SMOKE else 8.0   # minimum total measured wall-clock
CONTENTION_SPREAD = 0.30  # (max-min)/median above this => contended


def _provenance() -> dict:
    """Fields every artifact carries: the cut policy the repo defaults
    to, the device the run found, and the host CPU count — a "CPU
    baseline" from a 4-core runner and one from a 96-core host are
    different numbers, and without this field the artifact can't say
    which it is."""
    import jax

    from fastdfs_tpu.ops.gear_cdc import CDC_POLICY_DEFAULT
    devs = jax.devices()
    return {"cdc_policy": CDC_POLICY_DEFAULT, "smoke": _SMOKE,
            "host_cpus": os.cpu_count(),
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)}}


def _ru():
    """getrusage snapshot for per-phase CPU accounting, or None where
    the stdlib resource module is unavailable (non-POSIX)."""
    try:
        import resource
        return resource.getrusage(resource.RUSAGE_SELF)
    except Exception:
        return None


def _ru_delta(a, b) -> dict | None:
    """Named user/system CPU seconds burned between two _ru() snaps.
    Pairs with phase_wall_s: a phase whose wall time dwarfs its CPU
    time was WAITING (device, disk, contention), not computing — the
    distinction phase_wall_s alone cannot make."""
    if a is None or b is None:
        return None
    return {"utime_s": round(b.ru_utime - a.ru_utime, 3),
            "stime_s": round(b.ru_stime - a.ru_stime, 3)}


def _phase_rusage(marks: dict) -> dict:
    """{"phase_rusage": {...}, "maxrss_kb": N} from ordered phase-name
    -> _ru() snapshot marks (first mark is the baseline)."""
    names = list(marks)
    out = {}
    for prev, cur in zip(names, names[1:]):
        d = _ru_delta(marks[prev], marks[cur])
        if d is not None:
            out[cur] = d
    last = marks[names[-1]]
    return {"phase_rusage": out,
            "maxrss_kb": getattr(last, "ru_maxrss", None)}


def _bench_tpu(interpret: bool) -> dict:
    import jax

    from fastdfs_tpu.ops.pallas_minhash import minhash_batch_pallas
    from fastdfs_tpu.ops.pallas_sha1 import sha1_batch_pallas

    L = CHUNK_KB * 1024
    rng = np.random.RandomState(0)
    chunks = rng.randint(0, 256, size=(N_CHUNKS, L), dtype=np.uint8)
    lens = np.full(N_CHUNKS, L, dtype=np.int32)

    t_gen = time.perf_counter()
    ru = {"start": _ru()}
    dev_chunks = jax.device_put(chunks)
    dev_lens = jax.device_put(lens)
    jax.block_until_ready((dev_chunks, dev_lens))

    @jax.jit
    def step(c, ln):
        # sub=1: the smoke's 32 chunks fit one (1, 128) tile.
        return (sha1_batch_pallas(c, ln, L, sub=1 if _SMOKE else 16,
                                  interpret=interpret),
                minhash_batch_pallas(c, ln, interpret=interpret))

    # warmup/compile (and force one full execution)
    t_warm = time.perf_counter()
    ru["device_put"] = _ru()
    jax.device_get(step(dev_chunks, dev_lens))
    t_measure = time.perf_counter()
    ru["warmup_compile"] = _ru()

    rates = []
    t_total = 0.0
    while len(rates) < MAX_ROUNDS and (len(rates) < MIN_ROUNDS or
                                       t_total < MIN_SECONDS):
        t0 = time.perf_counter()
        outs = [step(dev_chunks, dev_lens) for _ in range(PIPELINE)]
        jax.device_get(outs)  # the fence: results are needed on the host
        dt = time.perf_counter() - t0
        t_total += dt
        rates.append(N_CHUNKS * L * PIPELINE / dt / 1e9)

    srt = sorted(rates)
    n = len(srt)
    median = srt[n // 2]
    q1, q3 = srt[n // 4], srt[(3 * n) // 4]
    spread = (srt[-1] - srt[0]) / median if median else 0.0
    contended = spread > CONTENTION_SPREAD
    out = {
        "value": round(median, 4),
        "rounds": n,
        "measured_seconds": round(t_total, 2),
        "dispersion": {
            "min": round(srt[0], 4),
            "median": round(median, 4),
            "max": round(srt[-1], 4),
            "iqr_rel": round((q3 - q1) / median, 4) if median else 0.0,
            "spread_rel": round(spread, 4),
        },
        "contended": contended,
        "contention_rule": f"(max-min)/median > {CONTENTION_SPREAD}",
        # Evidence trail (ISSUE 6 satellite): per-phase wall-times, so a
        # regressed headline number says WHERE the time moved (device
        # transfer? compile? the measured loop itself?) instead of
        # arriving as a bare rate.
        "phase_wall_s": {
            "device_put": round(t_warm - t_gen, 3),
            "warmup_compile": round(t_measure - t_warm, 3),
            "measure": round(time.perf_counter() - t_measure, 3),
        },
        # Warmup is a separate, named phase — never part of the measured
        # rounds (a number must say what it does and does not include).
        "warmup": {"rounds": 1, "wall_s": round(t_measure - t_warm, 3),
                   "in_measure": False},
    }
    ru["measure"] = _ru()
    out.update(_phase_rusage(ru))
    if contended:
        # Steady-state estimate when the capture straddled a contention
        # episode: the slow rounds are stalls, not kernel time.
        upper = srt[n // 2:]
        out["value_uncontended"] = round(upper[len(upper) // 2], 4)
    return out


def _bench_cpu(n_chunks: int = 256) -> float:
    L = CHUNK_KB * 1024
    rng = np.random.RandomState(0)
    data = rng.randint(0, 256, size=(n_chunks, L), dtype=np.uint8)
    rows = [row.tobytes() for row in data]
    t0 = time.perf_counter()
    for row in rows:
        hashlib.sha1(row).digest()
    dt = time.perf_counter() - t0
    return n_chunks * L / dt / 1e9


def _bench_multichip() -> dict:
    """Fan-out leg: the ``parallel.make_fingerprint_step`` shard_map over
    1 device and over ALL local devices, per-chip and aggregate GB/s.

    On a TPU host this measures real chip scaling at the full batch
    geometry.  Under ``_FDFS_BENCH_SMOKE=1`` the geometry shrinks — the
    XLA SHA1's unrolled 80-round graph costs minutes of compile per
    shape at 64 KB rows on CPU — so a CPU rehearsal validates the
    fan-out plumbing, never a throughput.  With a single local device
    the leg degrades to scaling 1.0 and says so.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from fastdfs_tpu.parallel.ingest_step import (fingerprint_mesh,
                                                  make_fingerprint_step)

    n_dev = len(jax.local_devices())
    if _SMOKE:
        L, n_rows, rounds = 256, 64, 1
    else:
        L, n_rows, rounds = CHUNK_KB * 1024, N_CHUNKS, 5
    n_rows = max(n_rows - n_rows % max(n_dev, 1), n_dev)
    rng = np.random.RandomState(0)
    chunks = rng.randint(0, 256, size=(n_rows, L), dtype=np.uint8)
    lens = np.full(n_rows, L, dtype=np.int32)

    legs = {}
    t_warm_total = 0.0
    ru = {"start": _ru()}
    for k in sorted({1, n_dev}):
        mesh = fingerprint_mesh(k)
        step = make_fingerprint_step(mesh, num_perms=64, shingle=5)
        # Data resident on the mesh before the clock starts: this leg
        # prices the compute fan-out, not the host link (the single-chip
        # bench already owns transfer accounting).
        dev_c = jax.device_put(chunks, NamedSharding(mesh, P("dp", None)))
        dev_l = jax.device_put(lens, NamedSharding(mesh, P("dp")))
        t0 = time.perf_counter()
        jax.block_until_ready(step(dev_c, dev_l))   # warmup/compile
        t_warm_total += time.perf_counter() - t0
        rates = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            jax.block_until_ready(step(dev_c, dev_l))
            rates.append(n_rows * L / (time.perf_counter() - t0) / 1e9)
        srt = sorted(rates)
        legs[k] = {
            "aggregate_GBps": round(srt[len(srt) // 2], 4),
            "per_chip_GBps": round(srt[len(srt) // 2] / k, 4),
            "rounds": len(srt),
            "dispersion": {"min": round(srt[0], 4), "max": round(srt[-1], 4)},
        }
    ru["measure"] = _ru()
    agg_1 = legs[1]["aggregate_GBps"]
    agg_n = legs[n_dev]["aggregate_GBps"]
    out = {
        "value": agg_n,
        "aggregate_GBps": agg_n,
        "per_chip_GBps": legs[n_dev]["per_chip_GBps"],
        "aggregate_1dev_GBps": agg_1,
        "scaling_1_to_n": round(agg_n / agg_1, 4) if agg_1 else None,
        "legs": {str(k): v for k, v in legs.items()},
        "rows": n_rows, "row_bytes": L,
        "warmup": {"wall_s": round(t_warm_total, 3), "in_measure": False},
        **_phase_rusage(ru),
    }
    if n_dev == 1:
        out["note"] = ("single local device: scaling leg degenerate "
                       "(1-device fallback); see OPERATIONS.md for the "
                       "multi-chip procedure")
    return out


def main() -> int:
    from fastdfs_tpu import compile_cache

    compile_cache.configure()
    multichip = "--multichip" in sys.argv[1:]
    head = {"metric": ("dedup_ingest_GBps_multichip" if multichip
                       else "dedup_ingest_GBps_per_chip"), "unit": "GB/s"}
    prov = _provenance()  # a backend that cannot start raises here: rc 1
    on_chip = prov["device"]["platform"] == "tpu"
    no_chip = {**head, "ok": False, "value": None,
               "error": f"no TPU: jax found {prov['device']}", **prov}
    if not on_chip and not _SMOKE:
        print(json.dumps(no_chip))
        return 1

    if multichip:
        out = _bench_multichip()
    else:
        out = _bench_tpu(interpret=not on_chip)
        t_cpu = time.perf_counter()
        ru_cpu0 = _ru()
        cpu_gbps = _bench_cpu()
        out["phase_wall_s"]["cpu_baseline"] = round(
            time.perf_counter() - t_cpu, 3)
        d = _ru_delta(ru_cpu0, _ru())
        if d is not None:
            out.setdefault("phase_rusage", {})["cpu_baseline"] = d
        out["vs_baseline"] = round(out["value"] / cpu_gbps, 4)
        out["cpu_baseline_GBps"] = round(cpu_gbps, 4)
    if not on_chip:
        # Smoke rehearsal on the CPU: the leg ran to its end, and that is
        # all it shows.  Its rates are not the chip's and are not printed.
        print(json.dumps({**no_chip, "rehearsed": sorted(out)}))
        return 1
    print(json.dumps({**head, "ok": True, **prov, **out}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
