"""Access log: sum of reindex_us (the stored file read back whole and sent
through FingerprintChunks for its signature, before the reply) over the
window's UPLOAD_CHUNKS rows,
per logical MB acknowledged through the negotiated upload."""

from . import _negotiated


def read(cell: dict):
    return _negotiated.stage_ms_per_mb(cell, 133, "reindex_us")
