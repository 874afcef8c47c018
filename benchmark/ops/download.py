"""``download``: a file this client stored comes back through
``FdfsClient.download_to_buffer``; timed from the request to the last
byte, its SHA-1 checked after the clock has stopped."""

import hashlib


def send(cli, known: dict, key: str, data):
    return cli.download_to_buffer(known[key][0])


def settle(known: dict, key: str, data, got: bytes):
    same = hashlib.sha1(got).hexdigest() == known[key][1]
    return len(got), "ok" if same else "wrong", None
