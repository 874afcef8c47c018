"""One closed-loop client process of a cell.  Never touches JAX.

Started by ``run.py``; speaks JSON lines on stdin/stdout:

    -> {"cmd": "preload"}            <- {"preloaded": n, "bytes": b}
    -> {"cmd": "go", "start": t0, "stop": t1}   (CLOCK_MONOTONIC, shared)
                                     <- {"ops": [...], "making_s": s}
    -> {"cmd": "quit"}

The loop: take the next operation from the generator, ``(kind, key,
bytes or None)``, and hand it to ``ops/<kind>.py``: ``send`` goes through
``FdfsClient`` and is what the clock times, ``settle`` runs after the
clock has stopped (remember the file id, check a download's SHA-1).
Only then does the client go on.  Making content and settling are the
load generator's own time, ``making_s``.  No operation starts after
``stop``.

An op is ``[kind, key, bytes, t_send, t_done, verdict, file id or
null]`` with verdict "ok", "failed:<why>" (refused, timed out, connection
lost) or "wrong" (answered with other bytes than were stored).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

from fastdfs_tpu.client.client import FdfsClient  # noqa: E402


def say(**fields) -> None:
    sys.stdout.write(json.dumps(fields) + "\n")
    sys.stdout.flush()


def upload_when_active(cli: FdfsClient, data: bytes, limit_s: float) -> str:
    """The first upload of a run: the storage may not have joined yet."""
    deadline = time.monotonic() + limit_s
    while True:
        try:
            return cli.upload_buffer(data, ext="bin")
        except Exception:  # noqa: BLE001 — not ACTIVE yet: any refusal
            if time.monotonic() > deadline:
                raise
            time.sleep(0.2)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--client", type=int, required=True)
    ap.add_argument("--tracker", required=True)
    args = ap.parse_args()
    with open(args.traffic) as fh:
        traffic = json.load(fh)
    gen_mod = importlib.import_module("generators." + traffic["generator"])
    gen = gen_mod.Generator(traffic["params"], args.seed, args.client,
                            traffic["clients"])
    known: dict[str, tuple[str, str]] = {}   # key -> (file id, sha1 hex)
    preload = gen.preload()
    cli = FdfsClient([args.tracker], timeout=traffic.get("op_timeout_s", 120))
    say(ready=True, preload_files=len(preload))

    kinds: dict = {}

    def op_kind(kind: str):
        if kind not in kinds:
            kinds[kind] = importlib.import_module("ops." + kind)
        return kinds[kind]

    upload = op_kind("upload")       # what set-up stores with

    for line in sys.stdin:
        msg = json.loads(line)
        if msg["cmd"] == "preload":
            for i, (key, data) in enumerate(preload):
                fid = (upload_when_active(cli, data, 90.0) if i == 0
                       else upload.send(cli, known, json.dumps(key), data))
                upload.settle(known, json.dumps(key), data, fid)
            if preload:      # one warm download: the read path's first use
                key, data = preload[0]
                if cli.download_to_buffer(known[json.dumps(key)][0]) != data:
                    raise RuntimeError("a preloaded file read back wrong")
            say(preloaded=len(preload), bytes=sum(len(d) for _, d in preload))
            preload = []
        elif msg["cmd"] == "go":
            ops, making = [], 0.0
            while time.monotonic() < msg["start"]:
                time.sleep(0.001)
            while True:
                t_make = time.monotonic()
                if t_make >= msg["stop"]:
                    break
                kind, key, data = gen.next_op()
                op, jkey = op_kind(kind), json.dumps(key)
                size, ref = len(data) if data else 0, None
                t_send = time.monotonic()
                making += t_send - t_make
                try:
                    reply = op.send(cli, known, jkey, data)
                except Exception as e:  # noqa: BLE001 — count it, go on
                    t_done = time.monotonic()
                    verdict = f"failed:{type(e).__name__}: {e}"[:200]
                else:
                    t_done = time.monotonic()
                    size, verdict, ref = op.settle(known, jkey, data, reply)
                making += time.monotonic() - t_done
                ops.append([kind, key, size, t_send, t_done, verdict, ref])
            say(ops=ops, making_s=making)
        elif msg["cmd"] == "quit":
            break
    cli.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
