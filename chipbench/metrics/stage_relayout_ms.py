"""stage.relayout_ms: device self time of the ``relayout`` stage (moves
between the parameter tree and the flat buffer: ``flatten_buf``,
``master_views``, ``flatbuf.flatten_tree`` / ``unflatten_tree`` and the
kernels' buffer build) per step of the window."""
from metrics import _stages


def read(ctx):
    return _stages.per_step_ms(ctx, "relayout")
