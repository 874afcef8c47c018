#!/usr/bin/env python3
"""chip_smoke.py — the served ingest path, once, on the chip.

Starts what an operator starts (``fdfs_trackerd``, ``fdfs_storaged`` with
``dedup_mode = sidecar``, ``python -m fastdfs_tpu.sidecar``), drives it
through ``fastdfs_tpu.client.FdfsClient`` with a seeded corpus at the
shipped widths, and checks what comes back against the benchmark's plain
reference (``benchmark/reference.py``: ``hashlib.sha1`` over the spans its
NumPy and serial gear-CDC chunkers cut), computed here in the parent.

The sidecar is the ONE process that touches the device.  This parent
imports jax (the package does) but never initialises a backend: the
device line of the verdict is what the sidecar's ``stats`` reply says it
got, and the proof that the chip did the work is counters
(``fingerprint_bytes``, ``dedup.chunk_hits``, every eligible file stored
as a recipe), never the absence of an error — the daemon fails open to
flat storage when the sidecar is down, and that must not pass for a run.

Every earlier line of stdout is one JSON object per phase; the last line
is the verdict, ``{"ok": true, "device": {"platform": "tpu", "kind":
..., "count": N}}``.  Any failed phase, or a device that is not a TPU
running the Pallas kernels, gives ``"ok": false``, the reason on the line
before, and a non-zero exit.

    python chip_smoke.py               # one chip: the whole served path
    python chip_smoke.py --multichip   # four chips: fan-out 4 vs fan-out 1
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import shutil
import struct
import sys
import time
from dataclasses import dataclass

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import harness  # noqa: E402  (tests/harness.py: the one build + spawn routine)
from benchmark import reference  # noqa: E402
from benchmark.run import RecipeReader  # noqa: E402
from fastdfs_tpu import compile_cache  # noqa: E402
from fastdfs_tpu.client.client import FdfsClient  # noqa: E402
from fastdfs_tpu.client.conn import StatusError  # noqa: E402
from fastdfs_tpu.common.protocol import StorageCmd  # noqa: E402
from fastdfs_tpu.dedup.engine import DedupConfig, _bucket_len  # noqa: E402
from fastdfs_tpu.ops import gear_cdc  # noqa: E402
from fastdfs_tpu import sidecar as sidecar_mod  # noqa: E402

SCRATCH = os.path.join(REPO, "chip_smoke_run")
HB = "heart_beat_interval = 1\nstat_report_interval = 1"
CFG = DedupConfig()                    # the shipped widths; none is shrunk
SHIPPED_SEGMENT_BYTES = 64 << 20       # conf/storage.conf dedup_segment_bytes
CHUNK_THRESHOLD = 64 << 10             # conf/storage.conf dedup_chunk_threshold
SERIAL_SAMPLE_BYTES = 1 << 20          # per-byte Python referee: keep it small


class SmokeFailure(Exception):
    """A phase found something wrong; the message is the reason line."""


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def need(cond: bool, reason: str) -> None:
    if not cond:
        raise SmokeFailure(reason)


# -- corpus ------------------------------------------------------------------

@dataclass(frozen=True)
class Corpus:
    """What gets ingested.  The defaults are one node's minute of traffic
    (about 400 MB); the CPU rehearsal in tests/ hands in a tiny one."""
    seed: int = 0
    big_bytes: int = 80 << 20          # (a) one file > dedup_segment_bytes
    n_small: int = 320                 # (b) log-uniform over [lo, hi]
    small_lo: int = 4 << 10
    small_hi: int = 4 << 20
    n_edited_small: int = 48           # (c) second versions, localized edits
    segment_bytes: int = SHIPPED_SEGMENT_BYTES
    verify_chunks: int = 48            # one scrub batch (scrub.cc: <= 64)


def _edit(data: bytes, rng: np.random.Generator, n_overwrites: int) -> bytes:
    """A second version: a few overwritten windows plus one insertion, so
    most chunks survive byte-identical and the CDC has to resynchronise."""
    buf = bytearray(data)
    for pos in rng.integers(0, max(1, len(buf) - 512), n_overwrites):
        buf[pos:pos + 256] = rng.bytes(256)
    mid = len(buf) * 2 // 3
    return bytes(buf[:mid]) + rng.bytes(100) + bytes(buf[mid:])


def make_corpus(spec: Corpus) -> list[dict]:
    """[{name, data, original}] in upload order: originals, then versions."""
    rng = np.random.default_rng(spec.seed)
    files = [{"name": "big", "data": rng.bytes(spec.big_bytes),
              "original": None}]
    sizes = np.exp(rng.uniform(np.log(spec.small_lo), np.log(spec.small_hi),
                               spec.n_small)).astype(np.int64)
    sizes[0], sizes[-1] = spec.small_lo, spec.small_hi
    for i, size in enumerate(sizes):
        files.append({"name": f"small{i}", "data": rng.bytes(int(size)),
                      "original": None})
    # Versions of files big enough to chunk into a handful of pieces.
    editable = [i for i in range(1, len(files))
                if len(files[i]["data"]) >= 4 * CHUNK_THRESHOLD]
    step = max(1, len(editable) // max(1, spec.n_edited_small))
    versions = [0] + editable[::step][:spec.n_edited_small]
    for i in versions:
        src = files[i]
        files.append({"name": src["name"] + ".v2",
                      "data": _edit(src["data"], rng,
                                    24 if i == 0 else 2),
                      "original": i})
    return files


def widths(segment_bytes: int) -> dict:
    """The shipped widths, as ``benchmark/reference.py`` takes them."""
    return {"cdc_min_size": CFG.min_size, "cdc_avg_bits": CFG.avg_bits,
            "cdc_max_size": CFG.max_size,
            "dedup_segment_bytes": segment_bytes}


def serial_segments(data: bytes, w: dict) -> list[tuple[int, list[int]]]:
    """``reference.segment_cuts`` by the per-byte serial chunker."""
    seg = w["dedup_segment_bytes"]
    return [(base, reference.cuts_serial(
        data[base:base + seg], w["cdc_min_size"], w["cdc_avg_bits"],
        w["cdc_max_size"])) for base in range(0, len(data), seg)]


def _cache_entries(cache_dir: str) -> int:
    try:
        return len(os.listdir(cache_dir))
    except FileNotFoundError:
        return 0


def _device_line(stats: dict) -> dict:
    return {"platform": stats["backend"], "kind": stats["device_kind"],
            "count": stats["device_count"]}


# -- the one-chip path -------------------------------------------------------

def _verify_batch(sock: str, chunks: list[bytes]) -> tuple[bytes, float]:
    """One DEDUP_VERIFY batch as the scrubber sends it; the LAST expected
    digest is deliberately wrong.  Returns (mask, seconds)."""
    want = [hashlib.sha1(c).digest() for c in chunks]
    want[-1] = bytes(20)
    body = struct.pack(">q", len(chunks)) + b"".join(
        struct.pack(">q", len(c)) + d for c, d in zip(chunks, want)
    ) + b"".join(chunks)
    t0 = time.monotonic()
    status, mask = sidecar_mod.rpc(sock, StorageCmd.DEDUP_VERIFY, body,
                                   timeout=600)
    need(status == 0, f"DEDUP_VERIFY answered status {status}")
    return mask, time.monotonic() - t0


def run(spec: Corpus = Corpus(), sidecar_args: tuple[str, ...] = ()) -> int:
    """The whole served path on one chip.  Prints the phase lines and the
    verdict; returns the exit code."""
    return _finish(_served_path, spec, sidecar_args)


def _served_path(spec: Corpus, sidecar_args, stack) -> dict:
    cache_dir = compile_cache.configure()

    t0 = time.monotonic()
    harness.ensure_native_built()
    say("build", ok=True, seconds=round(time.monotonic() - t0, 1),
        storaged=os.path.relpath(harness.STORAGED, REPO))

    t0 = time.monotonic()
    files = make_corpus(spec)
    total = sum(len(f["data"]) for f in files)
    w = widths(spec.segment_bytes)
    for f in files:
        f["eligible"] = len(f["data"]) >= CHUNK_THRESHOLD
        f["recipe"] = (reference.recipe(f["data"], w)
                       if f["eligible"] else None)
    eligible_bytes = sum(len(f["data"]) for f in files if f["eligible"])
    buckets: dict[int, int] = {}
    for f in files:
        for ln, _ in f["recipe"] or ():
            b = _bucket_len(ln, CFG.min_size, CFG.max_size)
            buckets[b] = buckets.get(b, 0) + 1
    # The reference's vectorised chunker made those cuts; hold it to its
    # serial per-byte one on a sample (far too slow for the corpus).
    sampled = 0
    for f in files:
        if f["eligible"] and sampled + len(f["data"]) <= SERIAL_SAMPLE_BYTES:
            need(reference.recipe(f["data"], w, serial_segments(f["data"], w))
                 == f["recipe"],
                 f"NumPy and serial CDC referees disagree on {f['name']}")
            sampled += len(f["data"])
    need(sampled > 0, "no file small enough for the serial referee")
    need(len(files[0]["data"]) > spec.segment_bytes,
         "the big file does not span two segments")
    need(len(buckets) == 6, f"length buckets hit: {sorted(buckets)}")
    say("corpus", ok=True, seed=spec.seed, files=len(files),
        bytes=total, chunk_eligible_bytes=eligible_bytes,
        segment_bytes=spec.segment_bytes,
        chunks_by_bucket={str(k): buckets[k] for k in sorted(buckets)},
        serial_referee_bytes=sampled,
        seconds=round(time.monotonic() - t0, 1))

    # Sidecar: a cold start (whatever the cache held is reported), then a
    # second start that must find the first one's compiles.
    entries_before = _cache_entries(cache_dir)
    cold = harness.Sidecar(os.path.join(SCRATCH, "sc"), sidecar_args)
    stack.callback(cold.stop)
    cold_warmup, cold_ready = cold.warmup_s(), cold.ready_s
    cold.stop()
    sc = harness.Sidecar(os.path.join(SCRATCH, "sc"), sidecar_args)
    stack.callback(sc.stop)
    stats0 = sc.stats()
    say("sidecar", ok=True, compile_cache=cache_dir,
        cache_entries_before=entries_before,
        cache_entries_after=_cache_entries(cache_dir),
        cold_warmup_s=cold_warmup, warm_warmup_s=sc.warmup_s(),
        cold_ready_s=round(cold_ready, 1), warm_ready_s=round(sc.ready_s, 1),
        **{k: stats0[k] for k in ("backend", "device_kind", "device_count",
                                  "use_pallas", "fan_out")})

    extra = HB
    if spec.segment_bytes != SHIPPED_SEGMENT_BYTES:
        extra += f"\ndedup_segment_bytes = {spec.segment_bytes}"
    tr = harness.start_tracker(os.path.join(SCRATCH, "tr"))
    stack.callback(tr.stop)
    st = harness.start_storage(os.path.join(SCRATCH, "st"),
                               trackers=[f"127.0.0.1:{tr.port}"],
                               dedup_mode="sidecar", dedup_sidecar=sc.sock,
                               extra=extra)
    stack.callback(st.stop)
    cli = FdfsClient([f"127.0.0.1:{tr.port}"], timeout=300.0)
    stack.callback(cli.close)

    # Sidecar RSS against bytes shipped, sampled about every 1/8 of the
    # corpus: a leak per byte shows as a slope, buffers as a plateau.
    rss_before = sc.rss_mb()
    rss_series, shipped, next_sample = [], 0, total // 8
    t0 = time.monotonic()
    for i, f in enumerate(files):
        f["id"] = (harness.upload_retry(cli, f["data"], ext="bin") if i == 0
                   else cli.upload_buffer(f["data"], ext="bin"))
        shipped += len(f["data"])
        if shipped >= next_sample:
            rss_series.append([shipped, round(sc.rss_mb(), 1)])
            next_sample = shipped + total // 8
    ingest_s = time.monotonic() - t0
    say("ingest", ok=True, files=len(files), bytes=total,
        seconds=round(ingest_s, 2), MBps=round(total / ingest_s / 1e6, 1),
        sidecar_rss_mb_before=round(rss_before, 1),
        sidecar_rss_mb_after=round(sc.rss_mb(), 1),
        sidecar_rss_mb_by_bytes_shipped=rss_series)

    t0 = time.monotonic()
    for f in files:
        got = cli.download_to_buffer(f["id"])
        need(got == f["data"], f"{f['name']} ({f['id']}) read back "
             f"{len(got)} bytes that differ from the {len(f['data'])} "
             "uploaded")
    say("readback", ok=True, files=len(files), bytes=total,
        seconds=round(time.monotonic() - t0, 2))

    n_recipes = n_chunks = 0
    reader = RecipeReader(st.port)
    stack.callback(reader.close)
    for f in files:
        # None when stored flat; a torn body reads as an empty recipe
        stored, logical = reader.fetch(f["id"]) or (None, 0)
        if not f["eligible"]:
            need(stored is None, f"{f['name']} is under the chunk "
                 "threshold yet has a recipe")
            continue
        need(stored is not None, f"{f['name']} is chunk-eligible but was "
             "stored flat: the daemon fell back around the sidecar")
        need(stored == f["recipe"], f"recipe of {f['name']} differs from "
             "the hashlib/serial-CDC reference")
        need(logical == len(f["data"]), f"recipe of {f['name']} covers "
             f"{logical} bytes of {len(f['data'])}")
        n_recipes += 1
        n_chunks += len(stored)
    say("recipes", ok=True, files_compared=n_recipes,
        chunks_compared=n_chunks, big_file_chunks=len(files[0]["recipe"]))

    ranked = 0
    for f in files:
        if f["original"] is None:
            continue
        pairs = cli.near_dups(f["id"])
        want = files[f["original"]]["id"]
        need(bool(pairs) and pairs[0][0] == want,
             f"NEAR_DUPS({f['name']}) ranked {pairs[:2]} first, not its "
             f"original {want}")
        ranked += 1
    need(ranked > 0, "the corpus holds no edited version")
    say("near_dups", ok=True, edited_files=ranked, original_ranked_first=ranked)

    victim = files[-1]
    cli.delete_file(victim["id"])
    try:
        cli.download_to_buffer(victim["id"])
    except StatusError as e:
        need(e.status == 2, f"deleted file answers status {e.status}")
    else:
        raise SmokeFailure(f"{victim['id']} still downloads after delete")
    say("delete", ok=True, file=victim["id"])

    # DEDUP_VERIFY, as the scrubber batches it (<= 64 chunks, <= 4 MB).
    # The shape (chunks, longest chunk) is new with every batch, so each
    # one compiles: time a batch, then the same batch again.
    big = files[0]["data"]
    chunks, off = [], 0
    for ln, _ in files[0]["recipe"][:spec.verify_chunks]:
        chunks.append(big[off:off + ln])
        off += ln
    mask, first_s = _verify_batch(sc.sock, chunks)
    mask2, again_s = _verify_batch(sc.sock, chunks)
    expect = bytes(len(chunks) - 1) + b"\x01"
    need(mask == expect and mask2 == expect,
         "DEDUP_VERIFY mask is wrong (every chunk but the last matches)")
    say("verify", ok=True, chunks=len(chunks),
        longest_chunk=max(map(len, chunks)),
        first_batch_s=round(first_s, 2), same_shape_again_s=round(again_s, 3))

    stats = sc.stats()
    reg = cli.storage_stat("127.0.0.1", st.port)
    counters = reg.get("counters", {})
    hits = counters.get("dedup.chunk_hits", 0)
    misses = counters.get("dedup.chunk_misses", 0)
    fallbacks = counters.get("ingest.recipe_fallbacks", 0)
    log = st.stderr_text + st.stdout_text
    fp_bytes = stats["fingerprint_bytes"] - stats0["fingerprint_bytes"]
    # Padded tile bytes the engine put on the device, warm-up taken off.
    placed = (sum(stats["device_bytes"].values())
              - sum(stats0["device_bytes"].values()))
    say("counters", ok=True, fingerprint_bytes=fp_bytes,
        chunk_eligible_bytes=eligible_bytes, device_bytes_placed=placed,
        sidecar_chunks=stats["chunks"],
        chunk_hits=hits, chunk_misses=misses, recipe_fallbacks=fallbacks,
        verify_host_fallbacks=stats["verify_host_fallbacks"],
        engine_s=round(stats["engine_us"] / 1e6, 2),
        sidecar_rss_mb_end=round(sc.rss_mb(), 1))
    need(fp_bytes >= eligible_bytes, f"sidecar fingerprinted {fp_bytes} "
         f"bytes, fewer than the {eligible_bytes} chunk-eligible uploaded")
    need(hits > 0, "dedup.chunk_hits is 0: the edited versions found no "
         "chunk of their originals")
    need(hits + misses == n_chunks, f"daemon judged {hits + misses} chunks, "
         f"the reference cut {n_chunks}")
    need(fallbacks == 0, f"ingest.recipe_fallbacks = {fallbacks}")
    need("fingerprint unavailable, storing flat" not in log,
         "the storage log says an upload was stored flat")
    need(stats["verify_host_fallbacks"] == 0,
         "the batched verify fell back to hashlib: "
         + sc.log_tail())
    return stats


# -- four chips: fan-out 4 against fan-out 1 ---------------------------------

MULTICHIP_SEGMENTS = 16
MULTICHIP_SEGMENT_BYTES = 4 << 20      # 16 x 4 MB = 64 MB


def _fingerprint_cuts(sock: str, session: int, seg: bytes,
                      cuts: list[int]) -> list[tuple[int, bytes]]:
    """DEDUP_FINGERPRINT_CUTS as the daemon sends it -> [(length, sha1)]."""
    body = (struct.pack(">qqq", session, 0, len(cuts))
            + struct.pack(f">{len(cuts)}q", *cuts) + seg)
    status, resp = sidecar_mod.rpc(sock, StorageCmd.DEDUP_FINGERPRINT_CUTS,
                                   body, timeout=600)
    need(status == 0, f"DEDUP_FINGERPRINT_CUTS answered status {status}")
    count = struct.unpack_from(">q", resp)[0]
    need(len(resp) == 8 + 36 * count, "fingerprint reply is torn")
    return [(struct.unpack_from(">q", resp, 8 + 36 * i + 8)[0],
             resp[8 + 36 * i + 16:8 + 36 * i + 36]) for i in range(count)]


def _fan_out_leg(fan_out: int, segments, sidecar_args) -> dict:
    """One sidecar at ``--fan-out N``: every segment through it, each
    committed as its own file so its signature lands in the snapshot."""
    base = os.path.join(SCRATCH, f"fan{fan_out}")
    shutil.rmtree(base, ignore_errors=True)  # no snapshot from another leg
    sc = harness.Sidecar(base, ("--fan-out", str(fan_out), *sidecar_args),
                 state_dir=os.path.join(base, "state"))
    try:
        stats0 = sc.stats()
        need(stats0["fan_out"] == fan_out,
             f"asked for fan-out {fan_out}, sidecar runs {stats0['fan_out']}")
        t0 = time.monotonic()
        digests = []
        for i, (seg, cuts) in enumerate(segments):
            digests.append(_fingerprint_cuts(sc.sock, i + 1, seg, cuts))
            status, _ = sidecar_mod.rpc(
                sc.sock, StorageCmd.DEDUP_COMMIT,
                f"commitchunks {i + 1} seg{i:02d}".encode())
            need(status == 0, "commitchunks refused")
        wall = time.monotonic() - t0
        stats = sc.stats()
        # Warm-up ran every bucket shape over zeros: not the corpus.
        stats["device_bytes"] = {
            dev: n - stats0["device_bytes"].get(dev, 0)
            for dev, n in stats["device_bytes"].items()}
    finally:
        sc.stop()  # SIGTERM: the sidecar snapshots its indexes on the way out
    near = np.load(os.path.join(base, "state", "sidecar_near.npz"),
                   allow_pickle=True)
    order = np.argsort([json.loads(str(r)) for r in near["refs"]])
    return {"stats": stats, "digests": digests, "wall_s": wall,
            "warmup_s": sc.warmup_s(), "sigs": near["sigs"][order]}


def run_multichip(seed: int = 0, sidecar_args: tuple[str, ...] = ()) -> int:
    return _finish(_multichip_path, seed, sidecar_args)


def _multichip_path(seed: int, sidecar_args, stack) -> dict:
    compile_cache.configure()
    rng = np.random.default_rng(seed)
    w = widths(MULTICHIP_SEGMENT_BYTES)
    segments, want = [], []
    for _ in range(MULTICHIP_SEGMENTS):
        seg = rng.bytes(MULTICHIP_SEGMENT_BYTES)
        cuts = gear_cdc.chunk_stream_np(seg, CFG.min_size, CFG.avg_bits,
                                        CFG.max_size)
        segments.append((seg, cuts))
        want.append(reference.recipe(seg, w))
    total = MULTICHIP_SEGMENTS * MULTICHIP_SEGMENT_BYTES
    say("corpus", ok=True, seed=seed, segments=len(segments), bytes=total,
        chunks=sum(map(len, want)))

    legs = {}
    for fan_out in (4, 1):  # one process each, never both alive
        leg = legs[fan_out] = _fan_out_leg(fan_out, segments, sidecar_args)
        per_device = leg["stats"]["device_bytes"]
        say(f"fan_out_{fan_out}", ok=True, bytes=total,
            wall_s=round(leg["wall_s"], 2),
            MBps=round(total / leg["wall_s"] / 1e6, 1),
            warmup_s=leg["warmup_s"], bytes_per_device=per_device,
            use_pallas=leg["stats"]["use_pallas"],
            engine_s=round(leg["stats"]["engine_us"] / 1e6, 2))
        need(leg["digests"] == want,
             f"fan-out {fan_out} digests differ from hashlib")
        placed = [b for b in per_device.values() if b > 0]
        need(len(placed) == fan_out, f"fan-out {fan_out} placed bytes on "
             f"{len(placed)} device(s): {per_device}")
    need(np.array_equal(legs[4]["sigs"], legs[1]["sigs"]),
         "MinHash signatures differ between fan-out 4 and fan-out 1")
    need(_device_line(legs[4]["stats"]) == _device_line(legs[1]["stats"]),
         "the two sidecars saw different devices")
    say("compare", ok=True, digests_equal_hashlib=True,
        signatures_equal=True, signature_rows=int(legs[4]["sigs"].shape[0]))
    # The verdict's device and use_pallas are the fan-out 1 sidecar's:
    # the one the fan-out step is compared with runs the Pallas kernels.
    return legs[1]["stats"]


# -- verdict -----------------------------------------------------------------

def _finish(path, *args) -> int:
    """Run ``path`` with a clean scratch dir, tear every child down, and
    print the verdict as the last line.  No failure ends in exit code 0."""
    shutil.rmtree(SCRATCH, ignore_errors=True)
    os.makedirs(SCRATCH)
    stats, reason = None, None
    with contextlib.ExitStack() as stack:
        try:
            stats = path(*args, stack)
        except Exception as e:  # noqa: BLE001 — boundary: report, tear down, fail
            reason = f"{type(e).__name__}: {e}"
    device = _device_line(stats) if stats else None
    if reason is None and not (device["platform"] == "tpu"
                               and stats["use_pallas"]):
        reason = (f"every phase ran, but on {device['platform']} with "
                  f"use_pallas={stats['use_pallas']}: not a TPU running the "
                  "Pallas kernels")
    if reason is not None:
        print(json.dumps({"failed": reason}), flush=True)
    else:
        shutil.rmtree(SCRATCH, ignore_errors=True)
    print(json.dumps({"ok": reason is None, "device": device}), flush=True)
    return 0 if reason is None else 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated corpus")
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: one sidecar at --fan-out 4, then one "
                         "at --fan-out 1, the same segments through both; "
                         "no other phase runs")
    args = ap.parse_args(argv)
    if args.multichip:
        return run_multichip(args.seed)
    return run(Corpus(seed=args.seed))


if __name__ == "__main__":
    raise SystemExit(main())
