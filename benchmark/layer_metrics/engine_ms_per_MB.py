"""Sidecar stats: engine_us (inside DedupEngine.fingerprint: pack, h2d,
kernels, d2h) over the window per fingerprinted MB."""

from ._per_mb import fingerprint_mb


def read(cell: dict):
    mb = fingerprint_mb(cell)
    return cell["sidecar_delta"]["engine_us"] / 1e3 / mb if mb else None
