"""Access log: sum of negotiate_us (recipe parse + PinAndMask: which chunks the
store lacks, the present ones pinned) over the window's UPLOAD_RECIPE rows,
per logical MB acknowledged through the negotiated upload."""

from . import _negotiated


def read(cell: dict):
    return _negotiated.stage_ms_per_mb(cell, 132, "negotiate_us")
