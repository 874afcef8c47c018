"""Client-side chunk fingerprinting for dedup-aware negotiated uploads.

The negotiated upload protocol (UPLOAD_RECIPE / UPLOAD_CHUNKS) moves the
fingerprint work from the storage daemon to the ingest edge: the client
chunks and hashes the payload locally, and only ships chunk bytes the
daemon's content-addressed store has never seen.  That dedups against
what the node stored itself only if the client cuts exactly as its node
cuts, so the client cuts with the node's own parameters and with nothing
else: :class:`ChunkingParams` is what the node answered to
``QUERY_CHUNKING`` (its ``dedup_cdc_widths``, its chunker's policy, its
``dedup_chunk_threshold`` and ``dedup_segment_bytes``), and
:func:`fingerprint_buffer` cuts every ``segment_bytes`` segment on its
own, as the daemon does (a segment end is a cut):

- cut points come from the shared gear CDC spec (``ops.gear_cdc``: one
  generated table, 32-byte window, identical greedy selection) — the
  NumPy twin ``chunk_stream_np`` on plain hosts, the JAX/Pallas
  ``chunk_stream`` when a TPU backend is up;
- digests are SHA1 over the raw chunk bytes — ``hashlib`` on plain
  hosts (C speed, no batch to amortize), ``ops.sha1.sha1_batch`` on TPU
  where the batched kernel amortizes the device round-trip.

A sidecar-mode node holds the recipe to its own cut of the stored bytes
before it acknowledges (``native/storage/ingest.cc:AssembleCommit``) and re-verifies
SHA1(payload) == digest of every shipped chunk, so a caller getting this
wrong cannot corrupt the store: it is refused and uploads plain.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

from fastdfs_tpu.common.protocol import unpack_chunking
from fastdfs_tpu.ops import gear_cdc


@dataclass(frozen=True)
class ChunkFingerprint:
    length: int
    digest: bytes  # 20-byte raw SHA1


@dataclass(frozen=True)
class ChunkingParams:
    """How one storage node cuts (``protocol.CHUNKING_FIELDS``)."""
    min_size: int
    avg_bits: int
    max_size: int
    cdc_policy: int
    chunk_threshold: int
    segment_bytes: int

    @classmethod
    def from_wire(cls, body: bytes) -> "ChunkingParams":
        return cls(**unpack_chunking(body))


# What conf/storage.conf ships, for tests and tools that have no node to
# ask.  An upload never cuts with these: it asks its node, and
# ``fingerprint_buffer`` has no default to fall back on.
SHIPPED_PARAMS = ChunkingParams(
    gear_cdc.DEFAULT_MIN_SIZE, gear_cdc.DEFAULT_AVG_BITS,
    gear_cdc.DEFAULT_MAX_SIZE, gear_cdc.CDC_POLICY_DEFAULT, 64 << 10,
    64 << 20)


def _tpu_up() -> bool:
    """True only when JAX is importable AND its default backend is a real
    TPU — a thin client on a CPU host must not pay a JAX import/compile
    just to fingerprint an upload."""
    try:
        import jax
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def _digests_tpu(data: bytes, cuts: list[int]) -> list[bytes] | None:
    """Batched SHA1 on the accelerator, bucketed by pow2 chunk length so
    each shape compiles once (the dedup engine's discipline).  None on
    any failure — the caller falls back to hashlib."""
    try:
        import numpy as np

        from fastdfs_tpu.ops.sha1 import sha1_batch

        out: list[bytes | None] = [None] * len(cuts)
        by_bucket: dict[int, list[int]] = {}
        start = 0
        spans = []
        for i, end in enumerate(cuts):
            spans.append((start, end))
            blen = 1
            while blen < end - start:
                blen <<= 1
            by_bucket.setdefault(blen, []).append(i)
            start = end
        for blen, idxs in by_bucket.items():
            batch = np.zeros((len(idxs), blen), dtype=np.uint8)
            lens = np.zeros(len(idxs), dtype=np.int32)
            for row, i in enumerate(idxs):
                s, e = spans[i]
                batch[row, : e - s] = np.frombuffer(data[s:e], dtype=np.uint8)
                lens[row] = e - s
            words = np.asarray(sha1_batch(batch, lens), dtype=np.uint32)
            raw = words.astype(">u4").tobytes()
            for row, i in enumerate(idxs):
                out[i] = raw[row * 20 : row * 20 + 20]
        return out  # type: ignore[return-value]
    except Exception:
        return None


def fingerprint_buffer(data: bytes,
                       params: ChunkingParams) -> list[ChunkFingerprint]:
    """CDC-chunk ``data`` and SHA1 each chunk, exactly as a node at
    ``params`` does: each ``segment_bytes`` segment is cut on its own.

    Returns one :class:`ChunkFingerprint` per chunk, in stream order
    (lengths sum to ``len(data)``).  Empty input -> empty list.
    """
    if not data:
        return []
    use_tpu = _tpu_up()
    chunker = gear_cdc.chunk_stream if use_tpu else gear_cdc.chunk_stream_np
    view = memoryview(data)
    cuts: list[int] = []
    for base in range(0, len(data), params.segment_bytes):
        # one copy of a segment, where the payload has more than one
        seg = (data if len(data) <= params.segment_bytes
               else bytes(view[base:base + params.segment_bytes]))
        cuts += [base + c for c in chunker(
            seg, params.min_size, params.avg_bits, params.max_size,
            cdc_policy=params.cdc_policy)]
    digests = _digests_tpu(data, cuts) if use_tpu else None
    if digests is None:
        digests = []
        start = 0
        for end in cuts:
            digests.append(hashlib.sha1(view[start:end]).digest())
            start = end
    out = []
    start = 0
    for end, dig in zip(cuts, digests):
        out.append(ChunkFingerprint(length=end - start, digest=dig))
        start = end
    return out
