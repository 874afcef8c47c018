#!/usr/bin/env python3
"""One run of one cell of the benchmark: the served path, timed from outside.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

starts what an operator starts (``fdfs_trackerd``, ``fdfs_storaged`` with
``dedup_mode = sidecar``, the dedup sidecar through
``benchmark/sidecar_launch.py``), generates the cell's traffic from
``--seed``, drives it from closed-loop client processes for ``--seconds``,
compares what the window stored with the plain reference
(``reference.py``), and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``compared`` (each number compared, beside its limit).

Everything that tells cells apart is data, found by the names in
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``generators/<kind>.py``, ``end_to_end/<metric>.py``,
``layer_metrics/<metric>.py``.  This file names no cell.

This process never initialises a JAX backend: the sidecar is the one
process on the chip, ``device`` is what its ``stats`` reply says it got,
and a sidecar that is not on a TPU fails the run (exit 2, no result).

CPU rehearsal, no chip needed (always ``correct: false``, exit 1)::

    python3 benchmark/run.py --workload backup_node.ingest --seed 1 \
        --seconds 3 --trace 1 --rehearse

``--rehearse`` passes ``--platform cpu`` to the sidecar and takes the
traffic's ``rehearse`` overrides (tiny sizes).  ``--control failopen``
(the daemon's sidecar socket is dead, so it stores flat) and ``--fault
digest|signature`` (see ``sidecar_launch.py``) break the run on purpose
for ``benchmark/tests``; no run of the benchmark passes them.  ``--dump-trace
FILE`` (with ``--trace 1``) writes the trace's planes, lines and heaviest
event names there: what a new kernel's name pattern is written against.
"""

from __future__ import annotations

import time

T0 = time.monotonic()      # set-up is counted from here

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import contextlib  # noqa: E402
import hashlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import multiprocessing  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import struct  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
RUN_DIR = os.path.join(HERE, "_run")
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

FLAT_LOG_LINE = "fingerprint unavailable, storing flat"

# Every wait has a limit of its own.
SIDECAR_LIMIT_S = 900.0     # reach the chip + warm up, on a cold compile cache
CLIENT_LIMIT_S = 240.0      # a client's reply outside the window (preload)
DRAIN_LIMIT_S = 150.0       # the last operations in flight after --seconds


class RunFailure(Exception):
    """The run cannot give a result; the message says why."""


def log(msg: str) -> None:
    print(f"[run {time.monotonic() - T0:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def parse_size(text: str) -> int:
    m = re.fullmatch(r"(\d+)([KMG]?)", text.strip())
    if not m:
        raise RunFailure(f"not a size: {text!r}")
    return int(m.group(1)) << {"": 0, "K": 10, "M": 20, "G": 30}[m.group(2)]


# -- the program under test, as an operator starts it ---------------------------

def write_conf(shipped: str, out_path: str, overrides: dict) -> dict:
    """The shipped conf with ``overrides`` applied (a list value repeats
    the key); returns the effective key -> last value."""
    left = dict(overrides)
    lines, effective = [], {}
    with open(shipped) as fh:
        for raw in fh:
            m = re.match(r"\s*([A-Za-z0-9_]+)\s*=\s*(.*?)\s*$", raw)
            if not m or raw.lstrip().startswith("#"):
                continue
            key, value = m.groups()
            if key in overrides:
                if key not in left:
                    continue            # a repeated key, already written
                value = left.pop(key)
            for v in value if isinstance(value, list) else [value]:
                lines.append(f"{key} = {v}")
                effective[key] = str(v)
    for key, value in left.items():
        for v in value if isinstance(value, list) else [value]:
            lines.append(f"{key} = {v}")
            effective[key] = str(v)
    with open(out_path, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    return effective


def end_process(proc: subprocess.Popen, limit_s: float) -> None:
    """SIGTERM, wait ``limit_s``, then SIGKILL; always reaped."""
    if proc.poll() is None:
        proc.terminate()
    try:
        proc.wait(timeout=limit_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()


class Sidecar:
    """The launcher child.  Every wait has a limit of its own."""

    def __init__(self, base: str, args: list[str], launcher_args: list[str]):
        os.makedirs(os.path.join(base, "state"))
        self.bench_dir = os.path.join(base, "bench")
        self.sock = os.path.join(base, "dedup.sock")
        self._sock_dir = None
        if len(self.sock.encode()) > 100:      # sun_path holds 108 bytes
            self._sock_dir = tempfile.mkdtemp(prefix="fdfsb_")
            self.sock = os.path.join(self._sock_dir, "dedup.sock")
        self.log_path = os.path.join(base, "sidecar.log")
        self.state_dir = os.path.join(base, "state")
        cmd = [sys.executable, os.path.join(HERE, "sidecar_launch.py"),
               "--bench-dir", self.bench_dir, *launcher_args, "--",
               "--socket", self.sock, "--state-dir", self.state_dir, *args]
        with open(self.log_path, "ab") as out:
            self.proc = subprocess.Popen(cmd, cwd=REPO, stdout=out,
                                         stderr=subprocess.STDOUT)

    def log_tail(self, n: int = 12) -> str:
        with contextlib.suppress(OSError), open(self.log_path,
                                                errors="replace") as fh:
            return " | ".join(fh.read().strip().splitlines()[-n:])
        return ""

    def wait_listening(self, limit_s: float) -> None:
        deadline = time.monotonic() + limit_s
        while True:
            if self.proc.poll() is not None:
                raise RunFailure(f"sidecar exited {self.proc.returncode} "
                                 f"before it listened: {self.log_tail()}")
            if os.path.exists(self.sock):
                with contextlib.suppress(OSError):
                    self.stats()
                    return
            if time.monotonic() > deadline:
                raise RunFailure(f"sidecar did not listen within {limit_s} s: "
                                 + self.log_tail())
            time.sleep(0.1)

    def stats(self) -> dict:
        from fastdfs_tpu.sidecar import read_stats
        return read_stats(self.sock)

    def signal_and_wait(self, sig: int, made: str, limit_s: float) -> None:
        path = os.path.join(self.bench_dir, made)
        with contextlib.suppress(FileNotFoundError):
            os.unlink(path)
        self.proc.send_signal(sig)
        deadline = time.monotonic() + limit_s
        while not os.path.exists(path):
            if self.proc.poll() is not None or time.monotonic() > deadline:
                raise RunFailure(f"launcher did not write {made} within "
                                 f"{limit_s} s: {self.log_tail()}")
            time.sleep(0.02)

    def stop(self) -> None:
        end_process(self.proc, 60.0)      # it writes its state on SIGTERM
        if self._sock_dir:
            shutil.rmtree(self._sock_dir, ignore_errors=True)


class Client:
    """One ``client_worker.py`` child and its JSON-line conversation."""

    def __init__(self, index: int, traffic_path: str, seed: int, tracker: str,
                 log_dir: str):
        self.index = index
        with open(os.path.join(log_dir, f"client{index}.err"), "ab") as err:
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "client_worker.py"),
                 "--traffic", traffic_path, "--seed", str(seed),
                 "--client", str(index), "--tracker", tracker],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=err,
                cwd=REPO, text=True)
        self.err_path = err.name

    def send(self, **msg) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def reply(self, limit_s: float) -> dict:
        box: list = []
        t = threading.Thread(target=lambda: box.append(
            self.proc.stdout.readline()), daemon=True)
        t.start()
        t.join(limit_s)
        if not box or not box[0]:
            with contextlib.suppress(OSError), open(self.err_path,
                                                    errors="replace") as fh:
                tail = fh.read()[-1500:]
            raise RunFailure(f"client {self.index} gave no reply within "
                             f"{limit_s} s: {tail}")
        return json.loads(box[0])

    def stop(self) -> None:
        end_process(self.proc, 5.0)


class RecipeReader:
    """FETCH_RECIPE (the opcode a rebuilding peer uses) over one kept
    connection.  A refusal for load (EBUSY: the admission ladder is still
    tight just after a window) is waited out, a minute at the most."""

    def __init__(self, port: int):
        from fastdfs_tpu.client.storage_client import StorageClient
        from fastdfs_tpu.common.protocol import PriorityClass

        def new():
            s = StorageClient("127.0.0.1", port, timeout=60.0)
            # FETCH_RECIPE is born BACKGROUND, the first class shed; the
            # check is the operator looking in, not recovery traffic.
            s.conn.priority = int(PriorityClass.CONTROL)
            return s
        self._new = new
        self._s = self._new()

    def close(self) -> None:
        self._s.close()

    def fetch(self, file_id: str):
        """([(length, sha1)], logical size), or None when stored flat."""
        from fastdfs_tpu.client.conn import StatusError
        from fastdfs_tpu.common.protocol import StorageCmd, pack_group_name
        group, remote = file_id.split("/", 1)
        deadline = time.monotonic() + 60.0
        while True:
            try:
                self._s.conn.send_request(
                    StorageCmd.FETCH_RECIPE,
                    pack_group_name(group) + remote.encode())
                body = self._s.conn.recv_response("fetch_recipe")
                break
            except StatusError as e:
                if e.status == 2:   # ENOENT: flat
                    return None
                if e.status != 16 or time.monotonic() > deadline:
                    raise
                time.sleep(0.25)
            except OSError:
                if time.monotonic() > deadline:
                    raise
                self._s.close()
                self._s = self._new()
        logical, count = struct.unpack_from(">qq", body)
        if len(body) != 16 + 28 * count:
            return [], logical      # torn: equals no reference
        return [(struct.unpack_from(">q", body, 16 + 28 * i + 20)[0],
                 body[16 + 28 * i:16 + 28 * i + 20])
                for i in range(count)], logical


def upload_rows(access_log: str) -> list[dict]:
    """The access log's upload rows (cmd 11), in the order they finished:
    <epoch> <ip> <cmd> <status> <bytes> <cost_us> <recv_us> <work_us>
    <fp_us> <fp_lock_us> <cswrite_us> <binlog_us> <req_bytes>."""
    rows = []
    with contextlib.suppress(FileNotFoundError), open(access_log) as fh:
        for line in fh:
            f = line.split()
            if len(f) < 13 or f[0].startswith("{") or f[2] != "11":
                continue
            rows.append({"status": int(f[3]), "bytes": int(f[4]),
                         "cost_us": int(f[5]), "recv_us": int(f[6]),
                         "work_us": int(f[7]), "fp_us": int(f[8]),
                         "fp_lock_us": int(f[9]), "cswrite_us": int(f[10]),
                         "binlog_us": int(f[11]), "req_bytes": int(f[12])})
    return rows


# -- the comparison that decides `correct` -----------------------------------------

def draw_sample(uploads: list, threshold: int, n: int, seed: int) -> list:
    """The longest acknowledged upload, then up to n-1 more drawn from
    the seed, half of them from the chunk-eligible ones."""
    import numpy as np
    if not uploads:
        return []
    order = sorted(range(len(uploads)), key=lambda i: -uploads[i]["bytes"])
    picked = [order[0]]
    rng = np.random.default_rng([seed, 99])
    eligible = [i for i in order[1:] if uploads[i]["bytes"] >= threshold]
    for pool, upto in ((eligible, 1 + n // 2), (order[1:], n)):
        pool = [i for i in pool if i not in picked]
        take = max(0, upto - len(picked))
        picked += [pool[j] for j in rng.permutation(len(pool))[:take]]
    return [uploads[i] for i in picked]


def describe_upload(job):
    """In a worker process: the bytes of one upload made again from the
    seed, and what the store must hold for them: (size, sha1 of the
    whole, recipe or None under the chunk threshold, signature)."""
    import reference
    kind, params, seed, client, n_clients, key, widths = job
    gen = importlib.import_module("generators." + kind).Generator(
        params, seed, client, n_clients)
    data = gen.content(key)
    whole = hashlib.sha1(data).digest()
    if len(data) < widths["dedup_chunk_threshold"]:
        return len(data), whole, None, None
    segs = reference.segment_cuts(data, widths)
    return (len(data), whole, reference.recipe(data, widths, segs),
            reference.file_signature(data, widths, segs))


def compare(cell: dict) -> dict:
    """Each number compared beside its limit: {name: [value, limit, how]}
    with how "max" (value <= limit) or "min" (value >= limit)."""
    import numpy as np
    import reference
    from fastdfs_tpu.client.client import FdfsClient
    from fastdfs_tpu.common.protocol import PriorityClass

    widths, traffic = cell["config"]["widths"], cell["traffic"]
    threshold = widths["dedup_chunk_threshold"]
    uploads, kept = cell["uploads"], cell["stored"]
    out = {}

    # A sample, the longest in it, is made again from the seed and
    # described by the reference in worker processes (NumPy and hashlib;
    # the clients have gone, the files are independent) while this
    # process reads the store.
    sample = draw_sample(kept, threshold, traffic["check_sample"],
                         cell["seed"])
    jobs = [(traffic["generator"], traffic["params"], cell["seed"],
             up["client"], traffic["clients"], up["key"], widths)
            for up in sample]

    def read_back(up):
        cli = FdfsClient([cell["tracker"]], timeout=120.0,
                         priority=int(PriorityClass.CONTROL))
        try:
            got = cli.download_to_buffer(up["file_id"])
        finally:
            cli.close()
        return len(got), hashlib.sha1(got).digest()

    t0 = time.monotonic()
    flat_eligible = short_recipes = chunked_small = 0
    recipe_bad = readback_bad = chunks = 0
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(6, max(1, len(jobs)))) as pool, \
            concurrent.futures.ThreadPoolExecutor(4) as readers:
        wanted = pool.imap(describe_upload, jobs)
        stored = readers.map(read_back, sample)

        # Every acknowledged upload of the window that is still there:
        # chunked iff eligible, and a recipe that covers its bytes.
        reader = RecipeReader(cell["storage_port"])
        for up in kept:
            got = reader.fetch(up["file_id"])
            if up["bytes"] >= threshold:
                if got is None:
                    flat_eligible += 1
                elif (got[1] != up["bytes"]
                      or sum(n for n, _ in got[0]) != up["bytes"]):
                    short_recipes += 1
                up["recipe"] = got[0] if got else None
            elif got is not None:
                chunked_small += 1
        reader.close()

        # The sample in full: recipe (cuts and SHA-1 of every chunk),
        # bytes read back, and (below) the MinHash signature.
        for up, got in zip(sample, stored):
            size, digest, want, sig = next(wanted)
            if got != (size, digest):
                readback_bad += 1
            if want is not None:
                chunks += len(want)
                if up.get("recipe") != want:
                    recipe_bad += 1
                up["want_sig"] = sig
    out["eligible_files_stored_flat"] = [flat_eligible, 0, "max"]
    out["recipes_not_covering_file"] = [short_recipes, 0, "max"]
    out["small_files_chunked"] = [chunked_small, 0, "max"]
    out["sample_files"] = [len(sample), 1, "min"]
    out["sample_recipes_differ"] = [recipe_bad, 0, "max"]
    out["sample_readback_differs"] = [readback_bad, 0, "max"]
    cell["sample_chunks"] = chunks
    cell["reference_s"] = time.monotonic() - t0

    # The signatures live in the sidecar: it writes them when it stops.
    cell["stop_program"]()
    sigs = {}
    near = os.path.join(cell["sidecar"].state_dir, "sidecar_near.npz")
    if os.path.exists(near):
        data = np.load(near, allow_pickle=True)
        for ref, sig in zip(data["refs"], data["sigs"]):
            sigs[json.loads(str(ref))] = sig      # the latest wins
    sig_bad = sig_seen = 0
    for up in sample:
        want = up.pop("want_sig", None)
        if want is None:
            continue
        sig_seen += 1
        got = sigs.get(up["file_id"])
        if got is None:
            sig_bad += bool((want != reference.EMPTY).any())
        elif not np.array_equal(np.asarray(got, np.uint32), want):
            sig_bad += 1
    out["sample_signatures_differ"] = [sig_bad, 0, "max"]
    cell["sample_signatures"] = sig_seen

    # Downloads inside the window, each checked by the client.
    out["window_downloads_wrong"] = [cell["wrong"], 0, "max"]

    # The proof that the chip did it.
    d = cell["sidecar_delta"]
    eligible_bytes = sum(u["bytes"] for u in uploads if u["bytes"] >= threshold)
    out["fingerprint_bytes_short"] = [
        max(0, eligible_bytes - d["fingerprint_bytes"]), 0, "max"]
    out["recipe_fallbacks"] = [cell["stat_delta"]["ingest.recipe_fallbacks"],
                               0, "max"]
    out["verify_host_fallbacks"] = [d["verify_host_fallbacks"], 0, "max"]
    out["stored_flat_log_lines"] = [cell["storage_log"].count(FLAT_LOG_LINE),
                                    0, "max"]
    if "min_chunk_hit_share" in traffic:
        # The duplicate share the generator put into the traffic has to
        # show as hits in the daemon's exact index.
        hits = cell["stat_delta"]["dedup.chunk_hits"]
        judged = hits + cell["stat_delta"]["dedup.chunk_misses"]
        out["chunk_hit_share"] = [hits / judged if judged else 0.0,
                                  traffic["min_chunk_hit_share"], "min"]
    return out


# -- one run --------------------------------------------------------------------------

def find_cell(name: str) -> dict:
    bench = load_json(REPO, "BENCHMARK.json")
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise RunFailure(f"BENCHMARK.json has no workload {name!r}")
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]
    return {"workload": w, "config": load_json(REPO, cfg["file"]),
            "traffic_path": os.path.join(HERE, "traffic",
                                         w["traffic"] + ".json"),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def run(args, stack: contextlib.ExitStack) -> tuple[dict, int]:
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import harness  # tests/harness.py: the program's own build + spawn routine

    cell = find_cell(args.workload)
    config = cell["config"]
    traffic = load_json(cell["traffic_path"])
    if args.rehearse:
        traffic["params"].update(traffic.get("rehearse", {}))
    cell.update(traffic=traffic, seed=args.seed)
    chips = cell["workload"]["chips"]

    shutil.rmtree(RUN_DIR, ignore_errors=True)
    os.makedirs(RUN_DIR)
    os.sync()      # an earlier run's writes are not this window's to flush
    stack.callback(shutil.rmtree, RUN_DIR, ignore_errors=True)
    traffic_path = os.path.join(RUN_DIR, "traffic.json")
    with open(traffic_path, "w") as fh:
        json.dump(traffic, fh)

    harness.ensure_native_built()
    log("native tree built")

    # Sidecar first: it takes longest (it reaches the chip, then warms up).
    sc_args = list(config.get("sidecar_args", []))
    if args.rehearse:
        sc_args += ["--platform", "cpu"]
    sidecar = Sidecar(os.path.join(RUN_DIR, "sc"), sc_args,
                      ["--bench-fault", args.fault] if args.fault else [])
    stack.callback(sidecar.stop)
    cell["sidecar"] = sidecar

    tr_dir, st_dir = os.path.join(RUN_DIR, "tr"), os.path.join(RUN_DIR, "st")
    os.makedirs(tr_dir)
    os.makedirs(st_dir)
    tr_port, st_port = harness.free_port(), harness.free_port()
    write_conf(os.path.join(REPO, "conf", "tracker.conf"),
               os.path.join(tr_dir, "tracker.conf"),
               {**config.get("tracker_conf", {}), "port": tr_port,
                "base_path": tr_dir})
    tracker = harness.Daemon(harness.TRACKERD,
                             os.path.join(tr_dir, "tracker.conf"), tr_port)
    stack.callback(tracker.stop)
    cell["tracker"] = f"127.0.0.1:{tr_port}"

    dead_sock = os.path.join(RUN_DIR, "nobody.sock")
    effective = write_conf(
        os.path.join(REPO, "conf", "storage.conf"),
        os.path.join(st_dir, "storage.conf"),
        {**config["storage_conf"], "port": st_port, "base_path": st_dir,
         "store_path0": st_dir, "tracker_server": [cell["tracker"]],
         "dedup_sidecar": (dead_sock if args.control == "failopen"
                           else sidecar.sock),
         "use_access_log": int(args.trace)})
    for key in ("dedup_chunk_threshold", "dedup_segment_bytes"):
        if parse_size(effective[key]) != config["widths"][key]:
            raise RunFailure(f"storage.conf {key} = {effective[key]}, the "
                             f"configuration states {config['widths'][key]}")
    storage = harness.Daemon(harness.STORAGED,
                             os.path.join(st_dir, "storage.conf"), st_port)
    stack.callback(storage.stop)
    cell["storage_port"] = st_port
    log("tracker and storage up")

    # The clients make their set-up content while the sidecar warms up.
    clients = [Client(i, traffic_path, args.seed, cell["tracker"], RUN_DIR)
               for i in range(traffic["clients"])]
    for c in clients:
        stack.callback(c.stop)

    sidecar.wait_listening(SIDECAR_LIMIT_S)
    stats0 = sidecar.stats()
    log(f"sidecar listening on {stats0['backend']}: {sidecar.log_tail(1)}")
    if not args.rehearse and not (stats0["backend"] == "tpu"
                                  and stats0["device_count"] >= chips):
        raise RunFailure(f"the sidecar got {stats0['device_count']} x "
                         f"{stats0['backend']}, the cell asks for {chips} TPU "
                         "chip(s)")

    def stop_program():
        """Storage first (it flushes its access log), then the sidecar
        (it writes its state).  Stopping twice does no harm."""
        storage.stop()
        sidecar.stop()
        cell["storage_log"] = storage.stderr_text + storage.stdout_text
    cell["stop_program"] = stop_program

    for c in clients:
        c.reply(CLIENT_LIMIT_S)                   # {"ready": true}
    for c in clients:
        c.send(cmd="preload")
    preloaded = [c.reply(CLIENT_LIMIT_S) for c in clients]
    n_preloaded = sum(p["preloaded"] for p in preloaded)
    log(f"preloaded {n_preloaded} files, "
        f"{sum(p['bytes'] for p in preloaded) / 1e6:.1f} MB")

    from fastdfs_tpu.client.client import FdfsClient
    admin = FdfsClient([cell["tracker"]], timeout=60.0)
    stack.callback(admin.close)

    def stat_counters() -> dict:
        reg = admin.storage_stat("127.0.0.1", st_port).get("counters", {})
        return {k: reg.get(k, 0) for k in (
            "dedup.chunk_hits", "dedup.chunk_misses",
            "ingest.recipe_fallbacks")}

    if args.trace:
        sidecar.signal_and_wait(signal.SIGUSR1, "trace_started", 60.0)
    stats1, stat1 = sidecar.stats(), stat_counters()
    t_trace0 = time.monotonic()

    # -- the window ------------------------------------------------------------
    t_start = time.monotonic() + 0.25
    t_stop = t_start + args.seconds
    setup_s = t_start - T0
    for c in clients:
        c.send(cmd="go", start=t_start, stop=t_stop)
    results = [c.reply(args.seconds + DRAIN_LIMIT_S) for c in clients]
    ops = [dict(zip(("kind", "key", "bytes", "t_send", "t_done", "verdict",
                     "file_id"), op), client=c.index)
           for c, res in zip(clients, results) for op in res["ops"]]
    t_end = max([t_stop] + [op["t_done"] for op in ops])
    t_trace1 = time.monotonic()
    sidecar.signal_and_wait(signal.SIGUSR2, "memory.json", 180.0)
    stats2, stat2 = sidecar.stats(), stat_counters()
    admission = admin.storage_admission_status("127.0.0.1", st_port)
    log(f"window closed: {len(ops)} operations in {t_end - t_start:.2f} s, "
        f"{sum(op['verdict'] != 'ok' for op in ops)} not ok; admission "
        f"level {admission.get('level')}, {admission.get('shed')} shed")

    for c in clients:
        c.stop()
    # What the window stored (kinds whose module says STORES), and what of
    # it a later operation of the window took away again (REMOVES).
    kinds = {k: importlib.import_module("ops." + k)
             for k in {op["kind"] for op in ops}}
    done = [op for op in ops if op["verdict"] == "ok"]
    uploads = [op for op in done if getattr(kinds[op["kind"]], "STORES", False)]
    removed = {(op["client"], json.dumps(op["key"])) for op in done
               if getattr(kinds[op["kind"]], "REMOVES", False)}

    cell.update(
        ops=ops, uploads=uploads,
        stored=[up for up in uploads
                if (up["client"], json.dumps(up["key"])) not in removed],
        window_s=t_end - t_start, setup_s=setup_s,
        making_s=[r["making_s"] for r in results],
        wrong=sum(op["verdict"] == "wrong" for op in ops),
        sidecar_delta={k: stats2[k] - stats1[k] for k in (
            "fingerprint_bytes", "chunks", "requests", "engine_us",
            "lock_wait_us", "verify_host_fallbacks")},
        placed_bytes=(sum(stats2["device_bytes"].values())
                      - sum(stats1["device_bytes"].values())),
        stat_delta={k: stat2[k] - stat1[k] for k in stat2},
        preloaded_files=n_preloaded)
    device = {"platform": stats2["backend"], "kind": stats2["device_kind"],
              "count": stats2["device_count"],
              **load_json(sidecar.bench_dir, "memory.json")}

    # -- after the window -----------------------------------------------------------
    compared = compare(cell)
    log(f"compared {len(cell['stored'])} uploads, sample of "
        f"{compared['sample_files'][0]} ({cell['sample_chunks']} chunks, "
        f"{cell['sample_signatures']} signatures) in "
        f"{cell['reference_s']:.1f} s")

    failed = sum(op["verdict"].startswith("failed") for op in ops)
    correct = all(v <= lim if how == "max" else v >= lim
                  for v, lim, how in compared.values())
    if args.rehearse or args.control or args.fault:
        # Not a run of the benchmark: whatever it found, it does not pass.
        compared["not_a_benchmark_run"] = [1, 0, "max"]
        passed = False
    else:
        passed = correct

    result = {"correct": passed, "attempted": len(ops), "failed": failed}
    if args.trace:
        import reduce_trace
        cell["access_rows"] = upload_rows(
            os.path.join(st_dir, "logs", "access.log"))[n_preloaded:]
        xplane = reduce_trace.find_xplane(os.path.join(sidecar.bench_dir,
                                                       "trace"))
        trace = reduce_trace.load(xplane) if xplane else None
        if trace and args.dump_trace:
            os.makedirs(os.path.dirname(args.dump_trace) or ".", exist_ok=True)
            with open(args.dump_trace, "w") as fh:
                json.dump(reduce_trace.outline(trace), fh, indent=1)
        cell["trace"] = reduce_trace.reduce(trace) if trace else None
        cell["trace_window_s"] = t_trace1 - t_trace0
        cell["peaks"] = load_json(HERE, "peaks.json")
        if cell["trace"]:
            device["busy_s"] = cell["trace"]["busy_s"]
            device["window_s"] = cell["trace_window_s"]
            heavy = sorted(cell["trace"]["ops"].items(),
                           key=lambda kv: -kv[1])[:10]
            result["breakdown"] = {
                "device_ops": [[k, v] for k, v in heavy],
                "idle_gaps": [["between device operations (host side not "
                               "attributed: no spans in the program)", g]
                              for g in cell["trace"]["gaps"]]}
        wanted, folder = cell["per_layer"], "layer_metrics"
    else:
        wanted, folder = cell["end_to_end"], "end_to_end"
    cell["device"] = device
    metrics = {}
    for m in wanted:
        reader = importlib.import_module(f"{folder}.{m['name']}")
        value = reader.read(cell)
        if value is not None:      # nothing to read: left out of the line
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    result["compared"] = {k: {"value": v, "limit": lim, "rule": how}
                          for k, (v, lim, how) in compared.items()}
    for k, (v, lim, how) in compared.items():
        print(f"compared {k}: {v} (limit: {how} {lim})", file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return result, 0 if passed else 1


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", choices=("failopen",))
    ap.add_argument("--fault", choices=("digest", "signature"))
    ap.add_argument("--dump-trace", metavar="FILE",
                    help="with --trace 1: write the trace's outline there")
    args = ap.parse_args(argv)
    result, code = None, 2
    with contextlib.ExitStack() as stack:
        try:
            result, code = run(args, stack)
        except Exception as e:  # noqa: BLE001 — boundary: tear down, fail
            print(f"run failed: {type(e).__name__}: {e}", file=sys.stderr,
                  flush=True)
            if not isinstance(e, RunFailure):
                import traceback
                traceback.print_exc()
    if result is not None:
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    raise SystemExit(main())
