"""Access log: sum of fp_us over the window's uploads, per uploaded MB.
fp_us is the daemon's fingerprint stage: native CDC plus the sidecar RPC."""

from ._per_mb import upload_rows_mb


def read(cell: dict):
    rows, mb = upload_rows_mb(cell)
    return sum(r["fp_us"] for r in rows) / 1e3 / mb if mb else None
