"""Access log: sum of present_us (chunks the store had: RefOne + ReadChunk +
CRC, one at a time) over the window's UPLOAD_CHUNKS rows,
per logical MB acknowledged through the negotiated upload."""

from . import _negotiated


def read(cell: dict):
    return _negotiated.stage_ms_per_mb(cell, 133, "present_us")
