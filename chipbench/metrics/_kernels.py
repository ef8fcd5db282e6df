"""Shared arithmetic of the kernel roofline readers.

A kernel's least bytes are counted from the work itself -- the flat
layout's padded length, the voters per chip, the dtypes of the pre-sign
direction, sign words, tally and master -- not from the kernel's
operands, so the share reads the same work after a later change fuses,
splits or removes a kernel.  The share is the least time those bytes
take at the chip's HBM peak over the device time of the kernel's events
in a step; a kernel that did not run reads nothing.
"""
from __future__ import annotations

import numpy as np

WORD_BITS = 32


def itemsize(dtype: str) -> int:
    return np.dtype(dtype).itemsize


def voters_per_chip(traffic: dict) -> int:
    """Voters whose signs one chip folds per step: the pod's devices
    (their words are gathered) times the clients of each."""
    return traffic["mesh"]["data"] * traffic["clients"]["count"]


def share(ctx, pattern: str, least_bytes_per_step: float):
    seconds = ctx.op_seconds(pattern)
    if seconds <= 0 or ctx.steps <= 0:
        return None
    least = least_bytes_per_step / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / ctx.steps)
