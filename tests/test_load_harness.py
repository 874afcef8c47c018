"""The native load harness (reference test/ directory: test_upload.c /
test_download.c / test_delete.c + combine_result.c).

fdfs_load drives a live cluster over the real wire protocol from C++
worker threads, records per-op latency lines, and `combine` merges them
into QPS + percentiles.  It is the operator's load tool (OPERATIONS.md,
"Load harness"); its arrival, connection-budget, priority and hot-set
flags are held here against a live daemon.
"""

import json
import os
import subprocess

import pytest

from harness import BUILD, ensure_native_built, start_storage, start_tracker, \
    upload_retry

from fastdfs_tpu.client.client import FdfsClient

LOAD = os.path.join(BUILD, "fdfs_load")
HB = "heart_beat_interval = 1\nstat_report_interval = 1"


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    ensure_native_built((LOAD,))
    tmp = tmp_path_factory.mktemp("load")
    tr = start_tracker(os.path.join(str(tmp), "tr"))
    st = start_storage(os.path.join(str(tmp), "st"),
                       trackers=[f"127.0.0.1:{tr.port}"],
                       dedup_mode="cpu", extra=HB)
    cli = FdfsClient([f"127.0.0.1:{tr.port}"])
    upload_retry(cli, b"warm", ext="bin")  # wait for ACTIVE
    yield tr, st, str(tmp)
    cli.close()
    st.stop()
    tr.stop()


def _combine(*results):
    out = subprocess.run([LOAD, "combine", *results],
                         stdout=subprocess.PIPE, check=True)
    return json.loads(out.stdout)


def test_upload_download_delete_cycle(cluster, tmp_path):
    tr, st, _ = cluster
    taddr = f"127.0.0.1:{tr.port}"
    res = str(tmp_path / "up.result")

    # 24 uploads of 64 KB over 4 worker threads, 12 distinct payloads
    # (every payload uploaded twice => exact-dedup bait).
    subprocess.run([LOAD, "upload", taddr, "24", "65536", "4", res, "12"],
                   check=True, timeout=120)
    up = _combine(res)
    assert up["ops"] == 24
    assert up["errors"] == 0
    assert up["qps"] > 0 and up["lat_p99_us"] >= up["lat_p50_us"] > 0
    ids_path = res + ".ids"
    ids = [ln for ln in open(ids_path).read().splitlines() if ln]
    assert len(ids) == 24
    assert all(id_.startswith("group1/M00/") for id_ in ids)

    # identical payloads deduplicate on the daemon: 12 distinct contents
    cli = FdfsClient([taddr])
    datas = {cli.download_to_buffer(i) for i in ids[:8]}
    assert all(len(d) == 65536 for d in datas)
    cli.close()

    # the download driver reads every id back through tracker routing
    dres = str(tmp_path / "down.result")
    subprocess.run([LOAD, "download", taddr, ids_path, "24", "4", dres],
                   check=True, timeout=120)
    down = _combine(dres)
    assert down["ops"] == 24 and down["errors"] == 0
    assert down["bytes"] == 24 * 65536

    # combine merges phases (multi-process aggregation path)
    both = _combine(res, dres)
    assert both["ops"] == 48

    # delete every id; a re-download must then fail
    xres = str(tmp_path / "del.result")
    subprocess.run([LOAD, "delete", taddr, ids_path, "4", xres],
                   check=True, timeout=120)
    dl = _combine(xres)
    assert dl["ops"] == 24 and dl["errors"] == 0
    cli = FdfsClient([taddr])
    with pytest.raises(Exception):
        cli.download_to_buffer(ids[0])
    cli.close()


# What each flag promises, on a live daemon: (flags, n_ops, check).
LOAD_FLAGS = {
    # op i is scheduled at t0 + i/R: the run cannot end before its schedule
    "open_loop": (["--open-loop", "--rate", "300"], 60,
                  lambda rep, pool: rep["wall_seconds"] >= 59 / 300 - 0.01),
    # one shared socket carries all four workers' traffic
    "conns_1": (["--conns", "1"], 40,
                lambda rep, pool: (pool["conns_budget"], pool["conns_peak"])
                == (1, 1) and pool["conns_opened"] >= 1),
    "conns_pool": (["--conns", "4"], 40,
                   lambda rep, pool: 1 <= pool["conns_peak"] <= 4
                   and pool["conns_opened"] >= 1),
    # classes are dealt by the op index; nothing is shed at this load
    "priority_mix": (["--priority-mix", "read:1:0.5,scan:4:0.5"], 60,
                     lambda rep, pool: set(rep["by_class"]) ==
                     {"interactive", "background"}
                     and sum(c["ops"] for c in rep["by_class"].values()) == 60
                     and all(c["ops"] > 0 and c["shed"] == 0
                             and c["admitted"] == c["ops"]
                             for c in rep["by_class"].values())),
    # the first 2 ids take 90% of the reads, and every record says which
    "hot_keys": (["--hot-keys", "2:90"], 100,
                 lambda rep, pool: rep["by_key_class"]["hot"]["ops"]
                 > rep["by_key_class"]["cold"]["ops"] > 0),
}


@pytest.fixture(scope="module")
def corpus_ids(cluster, tmp_path_factory):
    tr, _, _ = cluster
    res = str(tmp_path_factory.mktemp("corpus") / "up.result")
    subprocess.run([LOAD, "upload", f"127.0.0.1:{tr.port}", "16", "16384",
                    "4", res], check=True, timeout=120)
    return res + ".ids"


@pytest.mark.parametrize("case", LOAD_FLAGS)
def test_download_flags_on_a_live_cluster(cluster, corpus_ids, tmp_path, case):
    flags, n_ops, holds = LOAD_FLAGS[case]
    tr, _, _ = cluster
    res = str(tmp_path / "down.result")
    out = subprocess.run(
        [LOAD, "download", f"127.0.0.1:{tr.port}", corpus_ids, str(n_ops),
         "4", res, *flags], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    (pool,) = [json.loads(ln) for ln in out.stdout.splitlines()
               if ln.startswith('{"conns_budget"')]
    rep = _combine(res)
    assert rep["ops"] == n_ops and rep["errors"] == 0 and rep["shed"] == 0
    assert rep["bytes"] == n_ops * 16384
    assert holds(rep, pool), (rep, pool)


def test_open_loop_without_a_rate_is_refused(tmp_path):
    out = subprocess.run(
        [LOAD, "download", "127.0.0.1:1", str(tmp_path / "ids"), "1", "1",
         str(tmp_path / "out"), "--open-loop"], capture_output=True,
        timeout=60)
    assert out.returncode != 0 and b"--rate" in out.stderr
