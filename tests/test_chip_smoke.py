"""chip_smoke.py rehearsed on the CPU, and the pieces it leans on: the
sidecar's refusal to start without a TPU, the device fields of its
``stats`` reply, the counted verify fallback, the compile-cache helper.

The rehearsal drives the script's own ``run`` with a tiny corpus and
``--platform cpu`` for the sidecar (the script's command line has no such
switch): every phase must run and agree with the reference, and the
verdict must still be ``ok: false`` with a non-zero exit, because a CPU
is not the chip.
"""

import json
import os
import struct
import subprocess
import sys

import pytest

import chip_smoke
from fastdfs_tpu import compile_cache
from fastdfs_tpu.sidecar import DedupSidecar

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Shapes as shipped (gear CDC 2K/8K/64K, row_tile 256, 64 perms); only the
# byte count is small.  Three 1 MB segments stand in for the 64 MB ones,
# and a two-chunk verify batch for the scrubber's 48 (XLA's CPU backend
# takes a second per KB of the longest chunk to run the SHA-1 scan).
TINY = chip_smoke.Corpus(seed=3, big_bytes=(5 << 19) + 12345, n_small=14,
                         small_lo=4 << 10, small_hi=1 << 20,
                         n_edited_small=2, segment_bytes=1 << 20,
                         verify_chunks=2)


def test_chip_smoke_rehearsal_on_cpu_runs_every_phase_and_says_not_ok(capfd):
    rc = chip_smoke.run(TINY, sidecar_args=("--platform", "cpu"))
    lines = [json.loads(ln) for ln in capfd.readouterr().out.splitlines()
             if ln.startswith("{")]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert list(phases) == ["build", "corpus", "sidecar", "ingest",
                            "readback", "recipes", "near_dups", "delete",
                            "verify", "counters"], lines[-2:]
    assert all(p["ok"] for p in phases.values())
    # the device path's answers agreed with the hashlib/serial-CDC reference
    assert phases["recipes"]["chunks_compared"] > 300
    assert phases["recipes"]["big_file_chunks"] > 100
    assert phases["near_dups"]["original_ranked_first"] == 3
    assert (phases["counters"]["fingerprint_bytes"]
            >= phases["counters"]["chunk_eligible_bytes"] > 0)
    assert phases["counters"]["chunk_hits"] > 0
    assert phases["counters"]["recipe_fallbacks"] == 0
    assert phases["counters"]["verify_host_fallbacks"] == 0
    assert phases["sidecar"]["backend"] == "cpu"
    assert phases["sidecar"]["use_pallas"] is False
    # ...and none of that makes a CPU the chip.
    assert rc != 0
    assert "not a TPU" in lines[-2]["failed"]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert not any(ln.get("ok") is True and "device" in ln for ln in lines)


def test_sidecar_refuses_to_start_without_a_tpu(tmp_path):
    """No --platform, no TPU: a start-up error, not a hashlib service."""
    sock = os.path.join(str(tmp_path), "dedup.sock")
    proc = subprocess.run(
        [sys.executable, "-m", "fastdfs_tpu.sidecar", "--socket", sock],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr and "--platform cpu" in proc.stderr
    assert not os.path.exists(sock)


def test_stats_reply_carries_the_device_fields(tmp_path):
    sc = DedupSidecar(os.path.join(str(tmp_path), "x.sock"))
    status, body = sc._commit(b"stats")
    assert status == 0
    stats = json.loads(body)
    assert stats["backend"] == "cpu"
    assert stats["device_kind"] == "cpu"
    assert stats["device_count"] >= 1
    assert stats["use_pallas"] is False and stats["fan_out"] == 1
    assert stats["device_bytes"] == {}  # the host path places nothing
    assert stats["tiles_by_rows"] == {}
    assert stats["verify_host_fallbacks"] == 0
    assert stats["fingerprint_bytes"] == 0


def test_verify_host_fallback_is_counted_and_still_right(tmp_path, monkeypatch):
    import hashlib

    sc = DedupSidecar(os.path.join(str(tmp_path), "x.sock"))

    def broken(chunks):
        raise RuntimeError("device path down")

    monkeypatch.setattr(sc, "_batch_sha1", broken)
    chunks = [b"a" * 100, b"b" * 5000]
    want = [hashlib.sha1(chunks[0]).digest(), bytes(20)]
    body = struct.pack(">q", 2) + b"".join(
        struct.pack(">q", len(c)) + d for c, d in zip(chunks, want)
    ) + b"".join(chunks)
    status, mask = sc._verify(body)
    assert (status, mask) == (0, b"\x00\x01")
    assert sc.stats["verify_host_fallbacks"] == 1


@pytest.mark.parametrize("placed", ["/some/where/else", None])
def test_compile_cache_is_placed_from_outside_or_fixed_in_the_checkout(
        monkeypatch, placed):
    import jax

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.append((key, value)))
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        assert compile_cache.configure() == placed
        # no directory set in code: jax reads the variable
        assert not any(key == "jax_compilation_cache_dir"
                       for key, _ in updates)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        fixed = os.path.join(REPO, ".jax_cache")
        assert compile_cache.configure() == fixed
        assert fixed in [value for _, value in updates]
    # either way every program is kept: one that compiles in under JAX's
    # default 1 s would otherwise be compiled again at every start
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in updates
    assert ("jax_persistent_cache_min_entry_size_bytes", 0) in updates
