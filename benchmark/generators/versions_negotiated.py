"""``versions_negotiated``: the ``versions`` series (same sizes, same edit
model, same bytes for a seed), each generation of the window sent through
the negotiated upload: the client chunks and hashes at the edge, sends the
recipe, and ships only the chunks the node lacks (``ops/upload_negotiated``).
Set-up stores generation 0 by a plain upload, as for every cell.
"""

from __future__ import annotations

from . import versions


class Generator(versions.Generator):
    def next_op(self):
        """("upload_negotiated", key, bytes)."""
        _, key, data = super().next_op()
        return "upload_negotiated", key, data
