"""Shared by the per-MB readers."""


def upload_rows_mb(cell: dict):
    rows = [r for r in cell.get("access_rows") or () if r["status"] == 0]
    mb = sum(r["req_bytes"] for r in rows) / 1e6
    return rows, mb


def fingerprint_mb(cell: dict) -> float:
    return cell["sidecar_delta"]["fingerprint_bytes"] / 1e6
