"""Arithmetic shared by the latency readers: all operations of a kind in
the window, by the clients' clocks, a failed one at the worst latency."""

from __future__ import annotations


def latencies_ms(cell: dict, kind: str) -> list[float]:
    ops = [op for op in cell["ops"] if op["kind"] == kind]
    done = [(op["t_done"] - op["t_send"]) * 1e3 for op in ops]
    worst = max(done, default=0.0)
    return [worst if op["verdict"].startswith("failed") else ms
            for op, ms in zip(ops, done)]


def percentile(values: list[float], q: float) -> float | None:
    """Nearest-rank percentile; None when there is nothing to rank."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))     # ceil(n * q / 100)
    return ordered[int(rank) - 1]
