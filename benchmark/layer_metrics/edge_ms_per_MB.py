"""Clients' clocks less the daemon's: the summed latency of the window's
acknowledged negotiated uploads minus the summed ``cost_us`` of their two
requests in the access log, per logical MB: the client's own chunking and
hashing, its queries (tracker, the node's chunking parameters) and the
wire."""

from . import _negotiated


def read(cell: dict):
    got, mb = _negotiated.rows(cell), _negotiated.logical_mb(cell)
    if not got[133] or not mb:
        return None
    client_s = sum(up["t_done"] - up["t_send"] for up in cell["uploads"]
                   if up["kind"] == _negotiated.KIND)
    daemon_s = sum(r["cost_us"] for r in got[132] + got[133]) / 1e6
    return (client_s - daemon_s) * 1e3 / mb
