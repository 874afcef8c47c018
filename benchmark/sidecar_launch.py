"""Start ``fastdfs_tpu.sidecar`` so that the benchmark can trace the chip.

Only the process that holds the chip can trace it, and that process is the
sidecar.  This launcher IS that process: it installs three signal handlers
and then calls ``fastdfs_tpu.sidecar.main(argv)`` in its main thread, whose
accept loop wakes every 0.5 s, so a handler runs promptly there.

* SIGUSR1: ``jax.profiler.start_trace(<bench dir>/trace)``, then the file
  ``trace_started`` is written (the harness waits for it).
* SIGUSR2: ``stop_trace()`` if one runs, then ``memory.json`` is written:
  the peak bytes in use on the fullest local device.  The harness sends it
  at the end of every window, traced or not.

    python benchmark/sidecar_launch.py --bench-dir DIR [--bench-fault F] \
        -- <arguments of python -m fastdfs_tpu.sidecar>

``--bench-fault`` breaks the timed path underneath, for the tests under
``benchmark/tests`` that must see ``correct`` come out false; no run of
the benchmark passes it.  ``digest`` alters one SHA-1 word of one row in
every batch, ``signature`` one MinHash lane of every row.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _write(path: str, text: str) -> None:
    with open(path + ".tmp", "w") as fh:
        fh.write(text)
    os.replace(path + ".tmp", path)


def install_handlers(bench_dir: str) -> None:
    state = {"tracing": False}

    def start(*_):
        import jax
        if state["tracing"]:
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the device's lines are what is read
        opts.host_tracer_level = 1
        jax.profiler.start_trace(os.path.join(bench_dir, "trace"),
                                 profiler_options=opts)
        state["tracing"] = True
        _write(os.path.join(bench_dir, "trace_started"), "1")

    def stop(*_):
        import jax
        if state["tracing"]:
            jax.profiler.stop_trace()
            state["tracing"] = False
        peaks = []
        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        _write(os.path.join(bench_dir, "memory.json"),
               json.dumps({"memory_peak_bytes": max(peaks)}))

    signal.signal(signal.SIGUSR1, start)
    signal.signal(signal.SIGUSR2, stop)


def plant_fault(kind: str) -> None:
    import numpy as np

    from fastdfs_tpu.dedup.engine import DedupEngine

    inner = DedupEngine._fingerprint_batch

    def broken(self, batch, lens):
        d, s = inner(self, batch, lens)
        if kind == "digest":
            d = np.array(d)
            d[0, 0] ^= 1
        else:
            s = np.array(s)
            s[:, 0] ^= 1
        return d, s

    DedupEngine._fingerprint_batch = broken


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bench-dir", required=True)
    ap.add_argument("--bench-fault", choices=("digest", "signature"))
    ap.add_argument("sidecar_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    rest = args.sidecar_args[1:] if args.sidecar_args[:1] == ["--"] \
        else args.sidecar_args
    os.makedirs(args.bench_dir, exist_ok=True)
    install_handlers(args.bench_dir)
    if args.bench_fault:
        plant_fault(args.bench_fault)
    from fastdfs_tpu import sidecar
    return sidecar.main(rest)


if __name__ == "__main__":
    raise SystemExit(main())
