"""Shared by the readers of the negotiated upload's stages.

The daemon's access log has a row for each UPLOAD_RECIPE (132: the
negotiation) and UPLOAD_CHUNKS (133: the commit).  Set-up sends neither
(generation 0 goes in by a plain upload), so every acknowledged row of
the two is the window's.  After the columns ``host_spans.LATE`` names come
the negotiated upload's own: ``negotiate_us`` on a 132 row, ``present_us``,
``verify_us``, ``recipe_us``, ``reindex_us`` on a 133 row.  A daemon from
before them writes shorter rows, and a run without ``--trace 1`` writes no
log: the readers then have nothing to read and say so with ``None``.

The divisor is logical: the bytes of the uploads the clients saw
acknowledged through this path (what ``ingest_MBps`` counts).
"""

from __future__ import annotations

import contextlib
import os

COLUMNS = ("negotiate_us", "present_us", "verify_us", "recipe_us",
           "reindex_us")
FIRST = 16          # position of negotiate_us in a row
KIND = "upload_negotiated"


def rows(cell: dict) -> dict:
    """{132: [...], 133: [...]}: acknowledged rows as dicts of ``cost_us``,
    ``req_bytes`` and, where the log has them, ``COLUMNS``."""
    if "negotiated_rows" not in cell:
        run_dir = os.path.dirname(os.path.dirname(cell["sidecar"].bench_dir))
        out: dict = {132: [], 133: []}
        with contextlib.suppress(FileNotFoundError), open(os.path.join(
                run_dir, "st", "logs", "access.log")) as fh:
            for line in fh:
                f = line.split()
                if (len(f) < 13 or f[0].startswith("{")
                        or f[2] not in ("132", "133") or f[3] != "0"):
                    continue
                row = {"cost_us": int(f[5]), "req_bytes": int(f[12])}
                if len(f) >= FIRST + len(COLUMNS):
                    row.update(zip(COLUMNS, map(int, f[FIRST:])))
                out[int(f[2])].append(row)
        cell["negotiated_rows"] = out
    return cell["negotiated_rows"]


def logical_mb(cell: dict) -> float:
    return sum(up["bytes"] for up in cell["uploads"]
               if up["kind"] == KIND) / 1e6


def stage_ms_per_mb(cell: dict, cmd: int, column: str):
    got, mb = rows(cell)[cmd], logical_mb(cell)
    if not got or not mb or any(column not in r for r in got):
        return None
    return sum(r[column] for r in got) / 1e3 / mb
