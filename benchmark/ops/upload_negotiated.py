"""``upload_negotiated``: the bytes go in through
``FdfsClient.upload_buffer_dedup(..., min_dup_ratio=0)``: cut and hashed on
the client as its node cuts, recipe sent (UPLOAD_RECIPE), only the chunks
the node lacks shipped (UPLOAD_CHUNKS); timed from the first byte of that
to the file id.  An upload that quietly took the plain path instead (the
client's transparent fallback) is a failed operation here, not throughput.

A backup client holds no chip: this process is held to the CPU before
anything can ask JAX for a backend (``client/fingerprint.py:_tpu_up``
does), because on the benchmark's one host the chip is the sidecar's.
"""

import hashlib
import os

os.environ["JAX_PLATFORMS"] = "cpu"

STORES = True      # an acknowledged op leaves a file the comparison must find


def send(cli, known: dict, key: str, data: bytes):
    stats: dict = {}
    file_id = cli.upload_buffer_dedup(data, ext="bin", min_dup_ratio=0,
                                      stats=stats)
    return file_id, stats


def settle(known: dict, key: str, data: bytes, reply):
    """After the clock has stopped: -> (logical bytes, verdict, file id)."""
    file_id, stats = reply
    if stats.get("fallback"):
        return len(data), f"failed:fell back to plain ({stats['fallback']})", None
    known[key] = (file_id, hashlib.sha1(data).hexdigest())
    return len(data), "ok", file_id
