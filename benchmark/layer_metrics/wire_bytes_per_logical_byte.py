"""Access log: the request bodies of the window's acknowledged
UPLOAD_RECIPE and UPLOAD_CHUNKS rows (the recipe, then the chunks the node
lacked) over the logical bytes they stored: what the negotiated upload
puts on the wire for a byte of backup.  The loopback carries it at memory
speed, so ``ingest_MBps`` does not show it (the configuration's ``wire``
cut)."""

from . import _negotiated


def read(cell: dict):
    got, mb = _negotiated.rows(cell), _negotiated.logical_mb(cell)
    if not got[133] or not mb:
        return None
    return sum(r["req_bytes"] for r in got[132] + got[133]) / (mb * 1e6)
