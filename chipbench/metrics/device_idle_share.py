"""device.idle_share: the share of the traced window in which no
operation ran on the device (averaged over the cell's chips)."""


def read(ctx):
    if not ctx.ops:
        return None
    return 100.0 * ctx.idle_share
