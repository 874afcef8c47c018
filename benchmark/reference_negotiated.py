"""The plain reference of the negotiated upload's answer.

``reference.py`` says what the store must hold for a byte string; this
says what the protocol must answer when a client offers the next file: of
the next file's recipe, which chunks have to be shipped (the store holds
no chunk of that SHA-1 yet, and no earlier chunk of this same recipe
brings it) and how many bytes that is.  NumPy and ``hashlib`` only, by
way of ``reference``; nothing of the program.

A chunk that occurs twice in one recipe and is not in the store is asked
for at both places (the node answers from what its store holds when the
recipe arrives) and its bytes cross the wire twice; the node stores it
once.
"""

from __future__ import annotations

import reference


def held(recipes) -> set[bytes]:
    """The SHA-1s a store holds after storing files with these recipes."""
    return {sha for recipe in recipes for _, sha in recipe}


def shipped(store: set[bytes], recipe) -> tuple[list[int], int]:
    """(mask, bytes): mask[i] is 1 where chunk i of ``recipe`` must be
    shipped to a store holding ``store``; bytes is their total length."""
    mask = [int(sha not in store) for _, sha in recipe]
    return mask, sum(n for (n, _), need in zip(recipe, mask) if need)


def exchange(stored: list[bytes], data: bytes, widths: dict):
    """What uploading ``data`` to a node that stored ``stored`` (each as
    the reference cuts it) must come to: (recipe, mask, bytes shipped)."""
    recipe = reference.recipe(data, widths)
    mask, sent = shipped(held(reference.recipe(d, widths) for d in stored),
                         recipe)
    return recipe, mask, sent
