#!/usr/bin/env python3
"""The full-size comparison of ``crawl_neardup``: the served index's
answers at 30M rows against the plain reference scanning all of it.

    python3 benchmark/neardup_fullsize.py [--seed N] [--clients 2] [--strata 12]

Not a cell and not run by the driver: a run of the cell holds its checked
queries to the reference's answer over the family's stored generations
(``ops/near_dups.py``), on the argument that everything else in the index
is a stranger.  This script checks that argument, once, at the size the
configuration states.  It starts the program as ``run.py`` does (tracker,
storage daemon, the sidecar with the configuration's ``sidecar_args``:
the 30,000,000-row base on the chip), stores the first ``--strata``
strata of ``--clients`` clients of the cell's generator one after another
(so the index's rows are in the order of this list), then sends
``near_dups`` for every generation of every checked document and holds
each reply, line for line, to ``reference_neardup`` over **all** base rows,
made block by block from their rule, and all the rows it stored itself
(signatures by ``reference.py``'s NumPy MinHash).  Prints one JSON line;
exit 0 iff every reply is equal.  No chip: exit 2.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

import run  # noqa: E402  — the harness's own spawn routines


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=3900001997)
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--strata", type=int, default=12)
    ap.add_argument("--platform", default=None,
                    help="cpu: a rehearsal (give --base-rows too)")
    ap.add_argument("--base-rows", type=int, default=None)
    args = ap.parse_args(argv)

    import numpy as np

    import reference
    import reference_neardup
    from generators import revisits

    sys.path.insert(0, os.path.join(REPO, "tests"))
    import harness

    cell = run.find_cell("crawl_neardup.revisit")
    config, traffic = cell["config"], run.load_json(cell["traffic_path"])
    near, widths = config["near_index"], config["widths"]
    sc_args = list(config["sidecar_args"])
    base_rows = args.base_rows or near["rows"]
    sc_args[sc_args.index("--near-base") + 1] = \
        f"{base_rows}:{near['base_seed']}"
    if args.platform:
        sc_args += ["--platform", args.platform]

    shutil.rmtree(run.RUN_DIR, ignore_errors=True)
    os.makedirs(run.RUN_DIR)
    with contextlib.ExitStack() as stack:
        stack.callback(shutil.rmtree, run.RUN_DIR, ignore_errors=True)
        harness.ensure_native_built()
        sidecar = run.Sidecar(os.path.join(run.RUN_DIR, "sc"), sc_args, [])
        stack.callback(sidecar.stop)
        tr_dir = os.path.join(run.RUN_DIR, "tr")
        st_dir = os.path.join(run.RUN_DIR, "st")
        os.makedirs(tr_dir)
        os.makedirs(st_dir)
        tr_port, st_port = harness.free_port(), harness.free_port()
        run.write_conf(os.path.join(REPO, "conf", "tracker.conf"),
                       os.path.join(tr_dir, "tracker.conf"),
                       {"port": tr_port, "base_path": tr_dir})
        tracker = harness.Daemon(harness.TRACKERD,
                                 os.path.join(tr_dir, "tracker.conf"), tr_port)
        stack.callback(tracker.stop)
        run.write_conf(
            os.path.join(REPO, "conf", "storage.conf"),
            os.path.join(st_dir, "storage.conf"),
            {**config["storage_conf"], "port": st_port, "base_path": st_dir,
             "store_path0": st_dir,
             "tracker_server": [f"127.0.0.1:{tr_port}"],
             "dedup_sidecar": sidecar.sock})
        storage = harness.Daemon(harness.STORAGED,
                                 os.path.join(st_dir, "storage.conf"), st_port)
        stack.callback(storage.stop)
        sidecar.wait_listening(run.SIDECAR_LIMIT_S)
        stats = sidecar.stats()
        if not args.platform and stats["backend"] != "tpu":
            print(f"no TPU: the sidecar got {stats['backend']}",
                  file=sys.stderr)
            return 2

        from fastdfs_tpu.client.client import FdfsClient
        cli = FdfsClient([f"127.0.0.1:{tr_port}"], timeout=120)
        stack.callback(cli.close)

        # Store: one upload after another, so the index's own rows are in
        # the order of `stored`.
        stored = []                  # (file id, key, signature by the reference)
        first = True
        for c in range(args.clients):
            gen = revisits.Generator(traffic["params"], args.seed, c,
                                     traffic["clients"])
            for s in range(args.strata):
                for key in gen.stratum(s):
                    data = gen.content(key)
                    if first:
                        import client_worker
                        fid = client_worker.upload_when_active(cli, data, 90.0)
                        first = False
                    else:
                        fid = cli.upload_buffer(data, ext="bin")
                    stored.append((fid, key, reference.file_signature(
                        data, widths), gen.checked(key[1], key[2])))
        run.log(f"stored {len(stored)} documents over a base of "
                f"{stats['near_base_rows']} rows")

        # Ask: every generation of every checked document.
        asked = [(fid, key, sig) for fid, key, sig, checked in stored
                 if checked]
        replies = [[(ref, f"{score:.4f}") for ref, score in cli.near_dups(fid)]
                   for fid, _, _ in asked]
        stats = sidecar.stats()

    # The reference: all base rows in blocks, then the rows stored above.
    t0 = time.monotonic()
    own = ([fid for fid, *_ in stored],
           np.array([sig for _, _, sig, _ in stored], np.uint32))

    def all_rows():
        yield from reference_neardup.base_blocks(
            near["base_seed"], base_rows, widths["num_perms"])
        yield own
    ranked = reference_neardup.near_dups(
        [sig for _, _, sig in asked], all_rows(), near["bands"],
        near["near_dup_threshold"], 2 * near["near_dup_top_k"] + 1)
    want = [reference_neardup.reply_lines(fid, r, near["near_dup_top_k"])
            for (fid, _, _), r in zip(asked, ranked)]
    reference_s = time.monotonic() - t0

    family = {}
    for fid, key, *_ in stored:
        family.setdefault(tuple(key[:3]), []).append(fid)
    equal = sum(g == w for g, w in zip(replies, want))
    result = {
        "queries": len(asked), "equal": equal,
        "queries_of_families_with_three_stored_generations": sum(
            len(family[tuple(key[:3])]) == 3 for _, key, _ in asked),
        "lines_compared": sum(len(w) for w in want),
        "replies_naming_a_base_row": sum(
            any(str(ref).startswith("base/") for ref, _ in w) for w in want),
        "base_rows_scanned_by_the_reference": base_rows,
        "own_rows": len(stored), "reference_s": round(reference_s, 1),
        "near_rows": stats["near_rows"],
        "near_resident_bytes": stats["near_resident_bytes"],
        "memory_peak_bytes": stats["memory_peak_bytes"],
        "device": {"platform": stats["backend"],
                   "kind": stats["device_kind"]}}
    for (fid, key, _), g, w in zip(asked, replies, want):
        if g != w:
            print(f"differs: {key} {fid}: got {g}, want {w}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0 if asked and equal == len(asked) else 1


if __name__ == "__main__":
    raise SystemExit(main())
