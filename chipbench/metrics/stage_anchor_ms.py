"""stage.anchor_ms: device self time of the ``anchor`` stage (the round
boundary's anchor pass, ``compute_delta``: its forward/backward pass,
its relayouts and its edge and cloud means) per boundary step of the
window, which starts at one (steps / T_E of them, rounded up)."""
from metrics import _stages


def read(ctx):
    secs = _stages.seconds(ctx)
    boundaries = -(-ctx.steps // ctx.t_e)
    if secs is None or boundaries <= 0:
        return None
    return 1e3 * secs.get("anchor", 0.0) / boundaries
