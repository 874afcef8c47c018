"""``deck``: clients walk one deck of files in fixed proportions.

The deck holds ``classes`` (name, bytes, count) and is laid out in
``strata`` blocks that each hold the same share of every class, shuffled
within the block from the seed; a class with fewer files than blocks
names the blocks it falls in (``in_strata``).  So every seed sends the
same sizes at the same pace, in another order.  Every file is fresh
seeded bytes, fresh again on every wrap of the deck.

Entries are numbered through the wraps; client c takes entries = c mod
the number of clients.  Set-up uploads the first ``preload_entries``
entries; in the window a client alternates one upload (its next entry)
with one download of the entry it uploaded ``download_lag`` entries of
its own earlier, so downloads carry the same sizes as uploads.

A key is the entry's number.
"""

from __future__ import annotations

from .common import fresh_bytes, rng


class Generator:
    def __init__(self, params: dict, seed: int, client: int, n_clients: int):
        self.p, self.seed = params, seed
        self.client, self.n_clients = client, n_clients
        self._wraps: dict[int, list[int]] = {}
        strata = params["strata"]
        self._per_wrap = sum(c["count"] for c in params["classes"])
        for c in params["classes"]:
            if c["count"] % strata and len(c.get("in_strata", ())) != c["count"]:
                raise ValueError(f"deck: class {c['name']} neither divides "
                                 "over the strata nor names its own")
        self._mine = client            # next entry of this client's walk
        self._own: list[int] = []      # its entries so far, in order
        self._pending_download = False

    # -- the deck ------------------------------------------------------------

    def _wrap(self, w: int) -> list[int]:
        """The class index of every entry of wrap ``w``, in deck order."""
        if w not in self._wraps:
            strata, out = self.p["strata"], []
            for s in range(strata):
                block = []
                for ci, c in enumerate(self.p["classes"]):
                    n = (c["count"] // strata if c["count"] % strata == 0
                         else list(c["in_strata"]).count(s))
                    block += [ci] * n
                out += [block[j] for j in
                        rng(self.seed, 10, w, s).permutation(len(block))]
            self._wraps[w] = out
        return self._wraps[w]

    def content(self, key) -> bytes:
        w, i = divmod(int(key), self._per_wrap)
        size = self.p["classes"][self._wrap(w)[i]]["bytes"]
        return fresh_bytes(size, self.seed, 11, int(key))

    # -- one client's walk ------------------------------------------------------

    def _take(self) -> int:
        entry = self._mine
        self._mine += self.n_clients
        self._own.append(entry)
        return entry

    def preload(self):
        out = []
        while self._mine < self.p["preload_entries"]:
            entry = self._take()
            out.append((entry, self.content(entry)))
        return out

    def next_op(self):
        lag = self.p["download_lag"] // self.n_clients
        if self._pending_download and len(self._own) > lag:
            self._pending_download = False
            return "download", self._own[-1 - lag], None
        self._pending_download = True
        entry = self._take()
        return "upload", entry, self.content(entry)
