"""``near_dups``: which stored files resemble the one this client just
stored, through ``FdfsClient.near_dups`` (client -> daemon -> dio worker ->
sidecar -> the index on the device); timed from the request to the parsed
reply.  Stores nothing.

The key is the upload's, ``[client, birth, slot, generation]``, so
``known`` holds the file id of every generation of the family this client
has stored.  After the clock has stopped:

* a query with ``data`` is **checked** (``generators/revisits.py`` hands
  over the bytes of the family's generations so far): each generation's
  signature is computed with ``reference.py``'s NumPy MinHash (once, kept
  in ``known``), and the reply is held to ``reference_neardup``'s answer
  over the family's stored generations, line for line, score for score,
  in order.  Everything else the index holds is a stranger to the family
  (fresh seeded bytes, seeded base rows: a shared band of 4 x 32 bits has
  probability 2^-128), so the reference's answer over the family is its
  answer over the index; the full-size comparison
  (``neardup_fullsize.py``) checks that against all of it.  A difference
  is ``wrong``.
* every other query is held to form: only this family's file ids, scores
  descending and at least the threshold, at most ``2 * top_k`` lines.

An error status fails the operation in the caller (ENODATA comes back as
an empty reply from the client, and is right only where the reference has
no line either).
"""

from __future__ import annotations

import functools
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def send(cli, known: dict, key: str, data):
    return cli.near_dups(known[key][0])


@functools.lru_cache(maxsize=None)
def _config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as fh:
        return json.load(fh)


def family_ids(known: dict, key: str) -> dict[int, str]:
    """{generation: file id} of the family's generations this client holds."""
    client, birth, slot, _ = json.loads(key)
    return {g: known[k][0] for g in range(3)
            if (k := json.dumps([client, birth, slot, g])) in known}


def expected(known: dict, key: str, data: bytes) -> list[tuple[str, str]]:
    """The reference's reply for a checked query: [(file id, "<score>")]."""
    import reference
    import reference_neardup

    head, _, body = data.partition(b"\n")
    head = json.loads(head)
    cfg = _config(head["config"])
    widths, near = cfg["widths"], cfg["near_index"]
    client, birth, slot, gen = json.loads(key)
    ids = family_ids(known, key)
    refs, sigs, at = [], [], 0
    for g, size in zip(head["gens"], head["sizes"]):
        piece, at = body[at:at + size], at + size
        if g not in ids:           # its upload failed: the node never held it
            continue
        sig_key = "sig:" + json.dumps([client, birth, slot, g])
        if sig_key not in known:
            known[sig_key] = reference.file_signature(piece, widths)
        sig = known[sig_key]
        if (sig != reference.EMPTY).any():     # an empty one is not indexed
            refs.append(ids[g])
            sigs.append(sig)
    mine = known["sig:" + key]
    ranked = reference_neardup.near_dups(
        [mine], [(refs, np.array(sigs, np.uint32).reshape(-1, len(mine)))],
        near["bands"], near["near_dup_threshold"],
        2 * near["near_dup_top_k"] + 1)[0]
    return reference_neardup.reply_lines(ids[gen], ranked,
                                         near["near_dup_top_k"])


def well_formed(known: dict, key: str, reply, threshold: float = 0.5,
                most: int = 10) -> bool:
    own = set(family_ids(known, key).values()) - {known[key][0]}
    scores = [score for _, score in reply]
    return (len(reply) <= most and all(ref in own for ref, _ in reply)
            and all(s >= threshold for s in scores)
            and scores == sorted(scores, reverse=True))


def settle(known: dict, key: str, data, reply):
    """After the clock has stopped: -> (0, verdict, None)."""
    if data is None:
        return 0, "ok" if well_formed(known, key, reply) else "wrong", None
    got = [(ref, f"{score:.4f}") for ref, score in reply]
    return 0, "ok" if got == expected(known, key, data) else "wrong", None
