"""Access log: sum of verify_us (shipped chunks: host SHA-1 against the digest,
then PutAndRef) over the window's UPLOAD_CHUNKS rows,
per logical MB acknowledged through the negotiated upload."""

from . import _negotiated


def read(cell: dict):
    return _negotiated.stage_ms_per_mb(cell, 133, "verify_us")
