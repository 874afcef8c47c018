"""Share of the clients' window spent making content (the load
generator's own work), not waiting on the store.  Clients' clocks."""


def read(cell: dict):
    total = cell["window_s"] * len(cell["making_s"])
    return 100.0 * sum(cell["making_s"]) / total if total else None
