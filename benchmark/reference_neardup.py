"""The plain reference of the near-duplicate lookup: what ``near_dups``
must answer for a given signature over given rows.

NumPy only (and ``reference.py``'s murmur3 finalizer); nothing here
imports the program (``fastdfs_tpu``), JAX, or a table the program made.
The index's rule, as the configuration states it: a row is a candidate iff it shares one whole band (``bands`` bands of
``num_perms / bands`` lanes) with the query; its score is the count of
agreeing lanes over ``num_perms``; rows under ``threshold`` are dropped;
order is score descending, ties older row first; the reply is the best
``2 * top_k + 1`` with the asked file itself dropped, at most ``2 *
top_k`` lines ``<ref> <score:.4f>``.

Rows come as **sources in age order**, each ``(refs, sigs)``: ``sigs`` an
``(n, num_perms)`` uint32 block, ``refs`` its refs (a list, or a callable
``i -> ref`` so that thirty million refs are never held).  A scan never
holds more than one block: :func:`base_blocks` makes the rows of a
``--near-base`` from their rule, block by block.

* :func:`base_rows` — the seeded rows ``[start, stop)`` of a base: a
  counter hash of (seed, row, lane), murmur3's 32-bit finalizer of
  ``(row * num_perms + lane) ^ fmix32(seed)``.
* :func:`near_dups` — the ranked ``[(ref, score)]`` for one or many
  queries over the sources, and :func:`reply_lines`, the reply for a file.
* :func:`scan_bytes` — the bytes one pass over the index needs (each
  resident row read once), whatever implements the pass: what
  ``near_scan_roofline`` divides by the chip's HBM bandwidth.
"""

from __future__ import annotations

import numpy as np

from reference import EMPTY, _fmix32

BASE_PREFIX = "base/"


def base_rows(seed: int, start: int, stop: int, num_perms: int) -> np.ndarray:
    """``(stop - start, num_perms)`` uint32: rows ``[start, stop)`` of the
    base of ``seed``."""
    with np.errstate(over="ignore"):
        counter = (np.arange(start, stop, dtype=np.uint32)[:, None]
                   * np.uint32(num_perms)
                   + np.arange(num_perms, dtype=np.uint32)[None, :])
    mixed_seed = _fmix32(np.array([seed & 0xFFFFFFFF], dtype=np.uint32))[0]
    return _fmix32(counter ^ mixed_seed)


def base_blocks(seed: int, rows: int, num_perms: int, block: int = 1 << 19):
    """The whole base as sources: ``(i -> "base/<row>", sigs)`` a block."""
    for lo in range(0, rows, block):
        hi = min(rows, lo + block)
        yield ((lambda i, lo=lo: f"{BASE_PREFIX}{lo + i}"),
               base_rows(seed, lo, hi, num_perms))


def scan_bytes(rows: int, num_perms: int) -> int:
    """Every resident row's ``num_perms`` uint32 lanes, read once."""
    return rows * num_perms * 4


def _hits(query: np.ndarray, sigs: np.ndarray, bands: int, threshold: float):
    """(row indices, agreeing-lane counts) of the rows of ``sigs`` that
    share a whole band with ``query`` and reach the threshold."""
    n, perms = sigs.shape
    eq = sigs == query[None, :]
    whole = eq.reshape(n, bands, perms // bands).all(axis=2).any(axis=1)
    idx = np.flatnonzero(whole)
    counts = eq[idx].sum(axis=1)
    keep = counts / perms >= threshold
    return idx[keep], counts[keep]


def near_dups(queries, sources, bands: int, threshold: float, top_k: int
              ) -> list[list[tuple[object, float]]]:
    """For each query signature the best ``top_k`` ``(ref, score)`` over
    all ``sources`` (an iterable of ``(refs, sigs)`` in age order), by the
    module's rule.  An all-``EMPTY`` query has no answer."""
    queries = [np.asarray(q, np.uint32) for q in queries]
    found: list[list[tuple[int, int, object]]] = [[] for _ in queries]
    age = 0
    for refs, sigs in sources:
        sigs = np.asarray(sigs, np.uint32)
        for qi, query in enumerate(queries):
            if (query == EMPTY).all() or not len(sigs):
                continue
            idx, counts = _hits(query, sigs, bands, threshold)
            for i, c in zip(idx.tolist(), counts.tolist()):
                ref = refs(i) if callable(refs) else refs[i]
                found[qi].append((-c, age + i, ref))
        age += len(sigs)
    perms = len(queries[0]) if queries else 1
    return [[(ref, -neg / perms) for neg, _, ref in sorted(
        f, key=lambda t: t[:2])[:top_k]] for f in found]


def reply_lines(file_ref, ranked: list[tuple[object, float]], top_k: int
                ) -> list[tuple[object, str]]:
    """What ``near_dups <file_ref>`` answers, from the ranking of the
    file's own signature at ``2 * top_k + 1``: the file itself dropped,
    at most ``2 * top_k`` ``(ref, "<score:.4f>")``."""
    return [(ref, f"{score:.4f}") for ref, score in ranked
            if ref != file_ref][:2 * top_k]
