"""vote_update_roofline: the fused vote-and-update kernel's share of its
HBM roofline (``kernels/vote_update.py``): per step the sign words of
every voter of the edge are read, and the f32 master is read and
written once."""
from __future__ import annotations

from metrics import _kernels

PATTERN = r"^%vote_update\b"


def least_bytes(n_pad: int, voters: int, master_dtype: str = "float32"
                ) -> float:
    return n_pad * (voters / 8.0 + 2 * _kernels.itemsize(master_dtype))


def read(ctx):
    return _kernels.share(ctx, PATTERN, least_bytes(
        ctx.n_pad, _kernels.voters_per_chip(ctx.cell.traffic)))
