"""sign_pack_roofline: the sign-and-pack kernel's share of its HBM
roofline (``kernels/sign_pack.py``, merged client mode): per step each
local voter's pre-sign direction (gradient plus correction, in the
compute dtype) is read once and one bit per coordinate is written."""
from __future__ import annotations

from metrics import _kernels

PATTERN = r"^%sign_pack\b"


def least_bytes(n_pad: int, voters: int, grad_dtype: str) -> float:
    return n_pad * voters * (_kernels.itemsize(grad_dtype) + 1 / 8.0)


def read(ctx):
    tr = ctx.cell.traffic
    return _kernels.share(ctx, PATTERN, least_bytes(
        ctx.n_pad, tr["clients"]["count"], tr["compute_dtype"]))
