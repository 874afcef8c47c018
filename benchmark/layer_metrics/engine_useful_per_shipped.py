"""Sidecar stats: fingerprinted bytes over the padded tile bytes the
engine placed on the device for them (1 = no padding)."""


def read(cell: dict):
    placed = cell["placed_bytes"]
    return (cell["sidecar_delta"]["fingerprint_bytes"] / placed
            if placed else None)
