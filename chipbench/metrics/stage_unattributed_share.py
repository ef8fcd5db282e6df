"""stage.unattributed_share: the share of the device's busy time that no
stage scope claims (the step's loop and conditional themselves, the
participation mask, the loss metrics, ops with no ``op_name``)."""
from metrics import _stages


def read(ctx):
    secs = _stages.seconds(ctx)
    if secs is None:
        return None
    return 100.0 * secs[_stages.UNATTRIBUTED] / sum(secs.values())
