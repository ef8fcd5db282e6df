"""stage.grads_ms: device self time of the ``grads`` stage (the voters'
forward and backward passes, ``per_device_grads``) per step of the
window, the anchor's own pass excluded (it counts under ``anchor``)."""
from metrics import _stages


def read(ctx):
    return _stages.per_step_ms(ctx, "grads")
