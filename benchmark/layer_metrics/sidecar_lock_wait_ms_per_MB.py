"""Sidecar stats: lock_wait_us over the window per fingerprinted MB."""

from ._per_mb import fingerprint_mb


def read(cell: dict):
    mb = fingerprint_mb(cell)
    return cell["sidecar_delta"]["lock_wait_us"] / 1e3 / mb if mb else None
