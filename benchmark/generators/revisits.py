"""``revisits``: a crawl archive's arrivals.  Most are re-visits of a
document the node already holds, changed a little; every arrival is
stored and then asked about: which stored documents does it resemble.

Each client walks its own deck in strata.  Stratum ``s`` holds the new
documents born in it (``classes``: count and bytes of each size class,
fresh seeded bytes), generation 1 of the documents born ``revisit_lag``
strata earlier, and generation 2 of those born twice that earlier,
shuffled within the stratum from the seed: once the walk is
``2 * revisit_lag`` strata in, 2 of 3 uploads are re-visits and every
seed carries the same sizes at the same pace, in another order.
Generation g+1 is generation g edited by ``versions``' edit routine
(``edits_per_mib`` edits a MiB, each ``edit_min`` to ``edit_max`` bytes
replaced by as many fresh ones of another length in that range).

Set-up stores the first ``preload_strata`` strata by plain upload.  In the
window every upload is followed by ``near_dups`` for the file id it was
given, and only then does the client go on.  A document is **checked**
when its draw from the seed falls in 1 of ``check_one_in``: its query
carries the bytes of the family's generations so far (a JSON line of
their numbers and sizes, then the bytes), from which ``ops/near_dups``
computes the reference's answer after the clock has stopped.

A key is ``[client, birth stratum, slot, generation]``.
"""

from __future__ import annotations

import json

from . import versions
from .common import fresh_bytes, rng


class _Family:
    """What ``versions.Generator._edit`` reads of its generator: the edit
    parameters, the seed, and a number of the series (there the client,
    here one per document)."""

    def __init__(self, params: dict, seed: int, ident: int):
        self.p, self.seed, self.client = params, seed, ident


class Generator:
    def __init__(self, params: dict, seed: int, client: int, n_clients: int):
        self.p, self.seed = params, seed
        self.client, self.n_clients = client, n_clients
        self.sizes = [c["bytes"] for c in params["classes"]
                      for _ in range(c["count"])]       # by slot
        self._stratum = 0
        self._pending: list = []       # the ops left of the stratum in hand

    # -- documents --------------------------------------------------------------

    def _ident(self, birth: int, slot: int) -> int:
        return self.client + self.n_clients * (birth * len(self.sizes) + slot)

    def checked(self, birth: int, slot: int) -> bool:
        return int(rng(self.seed, 20, self._ident(birth, slot)).integers(
            self.p["check_one_in"])) == 0

    def content(self, key) -> bytes:
        """The bytes of ``key``, made again from the seed."""
        client, birth, slot, gen = key
        if client != self.client:
            raise ValueError("revisits: a generator makes its own client's keys")
        ident = self._ident(birth, slot)
        data = fresh_bytes(self.sizes[slot], self.seed, 21, ident)
        family = _Family(self.p, self.seed, ident)
        for g in range(1, gen + 1):
            data = versions.Generator._edit(family, data, g)
        return data

    # -- the walk -----------------------------------------------------------------

    def stratum(self, s: int) -> list[list[int]]:
        """The keys stratum ``s`` uploads, in its order."""
        lag = self.p["revisit_lag"]
        keys = [[self.client, s - g * lag, slot, g]
                for g in range(3) if s - g * lag >= 0
                for slot in range(len(self.sizes))]
        order = rng(self.seed, 22, self.client, s).permutation(len(keys))
        return [keys[i] for i in order]

    def preload(self):
        out = []
        while self._stratum < self.p["preload_strata"]:
            out += [(key, self.content(key))
                    for key in self.stratum(self._stratum)]
            self._stratum += 1
        return out

    def _family_blob(self, key) -> bytes:
        client, birth, slot, gen = key
        parts = [self.content([client, birth, slot, g])
                 for g in range(gen + 1)]
        head = {"gens": list(range(gen + 1)), "sizes": [len(p) for p in parts],
                "config": self.p["config"]}
        return json.dumps(head).encode() + b"\n" + b"".join(parts)

    def next_op(self):
        """("upload", key, bytes), then ("near_dups", key, the family's
        bytes for a checked document or None)."""
        if not self._pending:
            for key in self.stratum(self._stratum):
                self._pending += [("upload", key), ("near_dups", key)]
            self._stratum += 1
        kind, key = self._pending.pop(0)
        if kind == "upload":
            return kind, key, self.content(key)
        return kind, key, (self._family_blob(key)
                           if self.checked(key[1], key[2]) else None)
