"""Access log: sum of (cost_us - fp_us) over the window's uploads, per
uploaded MB: receive, tmp file, chunk store, recipe, binlog, reply."""

from ._per_mb import upload_rows_mb


def read(cell: dict):
    rows, mb = upload_rows_mb(cell)
    return (sum(r["cost_us"] - r["fp_us"] for r in rows) / 1e3 / mb
            if mb else None)
