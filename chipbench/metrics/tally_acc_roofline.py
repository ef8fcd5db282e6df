"""tally_acc_roofline: the streamed tally kernel's share of its HBM
roofline (``kernels/tally_acc.py``): per client and step the pre-sign
direction (gradient plus correction, in the compute dtype) is read
once, and the integer tally is read and written."""
from __future__ import annotations

from metrics import _kernels


PATTERN = r"^%tally_acc\b"


def tally_dtype(voters: int) -> str:
    return "int8" if voters <= 127 else "int32"


def least_bytes(n_pad: int, clients: int, grad_dtype: str,
                tally: str) -> float:
    per_client = (_kernels.itemsize(grad_dtype)
                  + 2 * _kernels.itemsize(tally))
    return n_pad * clients * per_client


def read(ctx):
    tr = ctx.cell.traffic
    return _kernels.share(ctx, PATTERN, least_bytes(
        ctx.n_pad, tr["clients"]["count"], tr["compute_dtype"],
        tally_dtype(_kernels.voters_per_chip(tr))))
