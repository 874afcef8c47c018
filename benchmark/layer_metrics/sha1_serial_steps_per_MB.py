"""Trace: the 64-byte SHA-1 blocks the kernel walked one after another (sum
of ``blocks`` over the window's fdfs.engine.dispatch spans), per fingerprinted
MB: what a MB costs the device in sequential steps, however many lanes ran
beside each other in each."""

import host_spans

from . import _dispatch


def read(cell: dict):
    got = _dispatch.for_cell(cell)
    spans = host_spans.for_cell(cell)
    if not got or not spans or not spans["fingerprint_mb"]:
        return None
    return got["blocks"] / spans["fingerprint_mb"]
