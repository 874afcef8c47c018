"""``upload``: the bytes go in through ``FdfsClient.upload_buffer`` (plain
upload, ``dedup_uploads`` off); timed from the send to the file id."""

import hashlib

STORES = True      # an acknowledged op leaves a file the comparison must find


def send(cli, known: dict, key: str, data: bytes):
    return cli.upload_buffer(data, ext="bin")


def settle(known: dict, key: str, data: bytes, file_id: str):
    """After the clock has stopped: -> (bytes moved, verdict, file id)."""
    known[key] = (file_id, hashlib.sha1(data).hexdigest())
    return len(data), "ok", file_id
