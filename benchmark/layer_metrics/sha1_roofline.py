"""The SHA-1 kernel's share of its HBM roofline (see _roofline.py)."""

from . import _roofline


def read(cell: dict):
    return _roofline.read(cell, "sha1_roofline")
