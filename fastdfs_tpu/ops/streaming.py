"""Double-buffered host→device streaming for the fingerprint pipeline.

The ingest path is a host-bandwidth problem as much as a kernel problem
(SURVEY.md §7 "hard parts"): the storage daemon receives bytes on the
host and the fingerprint kernels run on the device, so sustained
throughput requires the host→device transfer of batch ``i+1`` to
overlap the device compute of batch ``i``.  JAX transfers and
dispatches are asynchronous — ``device_put`` and a jitted call both
return futures — so double-buffering is expressed as a bounded
in-flight window: keep up to ``depth`` batches dispatched, fetch the
oldest only when the window is full.  With ``depth >= 2`` the transfer
of the next batch and the compute of the current one are concurrent by
construction; deeper windows additionally amortize per-dispatch
latency.

``DedupEngine.fingerprint`` applies the same bounded-window pattern to
its bucket batches (device arrays already resident, so no ``device_put``
step); this helper is the host-sourced variant for paths that stream raw
bytes to the device — the benchmark configs (``bench_configs.py``) drive
it, and ``tests/test_pallas_kernels.py`` pins its ordering semantics.
The reference's synchronous chunked-write loop
(``storage/storage_dio.c:dio_write_file()``) is the analogue being
replaced.
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Iterable, Iterator

import numpy as np


def stream_batches(batches: Iterable[tuple[np.ndarray, np.ndarray]],
                   step_fn: Callable,
                   depth: int = 2) -> Iterator[object]:
    """Run ``step_fn(device_batch, device_lens)`` over a host batch stream
    with up to ``depth`` batches in flight; yields fetched results in
    submission order.

    ``step_fn`` must be a jitted function (or any async-dispatching
    callable); its result pytree is fetched with ``jax.device_get``.
    """
    import jax

    if depth < 1:
        raise ValueError("depth must be >= 1")
    inflight: deque = deque()
    for batch, lens in batches:
        dev_b = jax.device_put(batch)
        dev_l = jax.device_put(lens)
        inflight.append(step_fn(dev_b, dev_l))
        if len(inflight) > depth:
            yield jax.device_get(inflight.popleft())
    while inflight:
        yield jax.device_get(inflight.popleft())
