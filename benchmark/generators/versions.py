"""``versions``: each client owns one series of archive generations.

Generation 0 is fresh seeded bytes; generation k+1 is generation k with
``edits_per_mib`` edits per MiB, each replacing a run of ``edit_min`` to
``edit_max`` bytes with fresh bytes of another length in that range, so
offsets shift and the chunker has to resynchronise.  Set-up uploads
generation 0 (``preload``); the window uploads generation 1, 2, ...

The series sizes are one fixed list (``sizes_mib``) dealt to the clients
in an order drawn from the seed: every seed carries the same work.

A key is ``[client, generation]``.
"""

from __future__ import annotations

from .common import fresh_bytes, rng

_MIB = 1 << 20


class Generator:
    def __init__(self, params: dict, seed: int, client: int, n_clients: int):
        self.p, self.seed, self.client = params, seed, client
        sizes = list(params["sizes_mib"])
        if len(sizes) != n_clients:
            raise ValueError("versions: one entry of sizes_mib per client")
        order = rng(seed, 0).permutation(len(sizes))
        self.size = int(sizes[order[client]] * _MIB)
        self.gen, self.data = -1, b""

    def _edit(self, data: bytes, gen: int) -> bytes:
        p, r = self.p, rng(self.seed, 1, self.client, gen)
        n = max(1, round(p["edits_per_mib"] * len(data) / _MIB))
        lo, hi = p["edit_min"], p["edit_max"]
        starts = sorted(int(x) for x in r.integers(0, len(data) - hi, n))
        cut, fresh = r.integers(lo, hi + 1, n), r.integers(lo, hi + 1, n)
        pieces, last = [], 0
        for i, at in enumerate(starts):
            if at < last:            # inside the run just replaced
                continue
            pieces.append(data[last:at])
            pieces.append(fresh_bytes(int(fresh[i]), self.seed, 2,
                                      self.client, gen, i))
            last = at + int(cut[i])
        pieces.append(data[last:])
        return b"".join(pieces)

    def _make(self, prev: bytes, gen: int) -> bytes:
        return (fresh_bytes(self.size, self.seed, 3, self.client)
                if gen == 0 else self._edit(prev, gen))

    def _advance(self) -> None:
        self.gen += 1
        self.data = self._make(self.data, self.gen)

    def preload(self):
        """[(key, bytes)] that set-up uploads, here generation 0."""
        self._advance()
        return [([self.client, 0], self.data)]

    def next_op(self):
        """("upload", key, bytes) or ("download", key, None)."""
        self._advance()
        return "upload", [self.client, self.gen], self.data

    def content(self, key) -> bytes:
        """The bytes of ``key``, made again from the seed."""
        client, gen = key
        if client != self.client:
            raise ValueError("versions: a generator makes its own client's keys")
        data = b""
        for g in range(gen + 1):
            data = self._make(data, g)
        return data
