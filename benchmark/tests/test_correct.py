"""The benchmark's own tests: ``python3 -m pytest benchmark/tests -q``.

They need no chip.  The runs below are CPU rehearsals (``--rehearse``:
the sidecar on ``--platform cpu``, tiny sizes); they skip the harness's
look for a TPU and drive the rest of a run.  A rehearsal never passes
for a benchmark run (``correct`` false in its line), so what the
comparison itself decided is read from the ``correct: ...`` line on
standard error and from the numbers under ``compared``.

* a sound run compares clean;
* the control (``--control failopen``: the daemon cannot reach the
  sidecar and stores flat, the program's own fail-open path, which breaks
  the configuration's "recipe complete" guarantee) comes out not correct;
* each fault a cell can have, planted under the timed path (an answer
  altered where it is produced: one SHA-1 word, one MinHash lane), comes
  out not correct, caught by the number that is there for it.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import reference  # noqa: E402

WIDTHS = {"cdc_min_size": 2048, "cdc_avg_bits": 13, "cdc_max_size": 65536,
          "shingle": 5, "num_perms": 64, "dedup_segment_bytes": 200000}


def rehearse(workload: str, *extra: str) -> tuple[int, dict, bool]:
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", "2147483659", "--seconds", "2", "--trace", "0",
         "--rehearse", *extra],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.stdout.strip(), proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    decided = [ln for ln in proc.stderr.splitlines()
               if ln.startswith("correct: ")][-1] == "correct: True"
    return proc.returncode, line, decided


def value(line: dict, name: str):
    return line["compared"][name]["value"]


@pytest.mark.parametrize("workload", ["backup_node.ingest",
                                      "upstream_mix.updown"])
def test_sound_rehearsal_compares_clean_and_never_passes(workload):
    code, line, decided = rehearse(workload)
    assert decided, line["compared"]
    assert code == 1 and line["correct"] is False
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    assert value(line, "sample_files") >= 1


def test_control_failopen_is_not_correct():
    _, line, decided = rehearse("backup_node.ingest", "--control", "failopen")
    assert not decided
    assert value(line, "eligible_files_stored_flat") > 0
    assert value(line, "fingerprint_bytes_short") > 0
    assert value(line, "stored_flat_log_lines") > 0


@pytest.mark.parametrize("fault,caught_by", [
    ("digest", "sample_recipes_differ"),
    ("signature", "sample_signatures_differ")])
def test_answer_altered_where_produced_is_not_correct(fault, caught_by):
    _, line, decided = rehearse("backup_node.ingest", "--fault", fault)
    assert not decided
    assert value(line, caught_by) > 0


def test_no_result_without_the_program(tmp_path):
    """Only BENCHMARK.json and the benchmark's directory: no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("_run", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "backup_node.ingest", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and not proc.stdout.strip()


def test_trace_reduction_on_the_fixture():
    proc = subprocess.run([sys.executable,
                           os.path.join(BENCH, "check_reduce.py")],
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout


def test_vectorised_cuts_equal_the_serial_chunker():
    rng = np.random.default_rng(11)
    data = rng.bytes(300_000) + bytes(70_000) + rng.bytes(3)
    assert (reference.cuts(data, 2048, 13, 65536)
            == reference.cuts_serial(data, 2048, 13, 65536))


def test_reference_signature_against_a_second_witness():
    """The program's XLA reference (not its Pallas kernel), on the CPU,
    chunk by chunk: a witness, not a source of the reference."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, REPO)
    from fastdfs_tpu.ops import minhash
    rng = np.random.default_rng(12)
    data = rng.bytes(250_000) + bytes(3)     # ends in a 3-byte chunk
    want = np.full(64, 0xFFFFFFFF, np.uint32)
    for base, ends in reference.segment_cuts(data, WIDTHS):
        last = 0
        for cut in ends:
            row = np.zeros((1, 65536), np.uint8)
            row[0, :cut - last] = np.frombuffer(
                data[base + last:base + cut], np.uint8)
            sig = minhash.minhash_batch(row, np.array([cut - last], np.int32),
                                        64, 5)
            want = np.minimum(want, np.asarray(sig)[0])
            last = cut
    assert np.array_equal(reference.file_signature(data, WIDTHS), want)
