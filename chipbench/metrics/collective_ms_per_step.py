"""collective.ms_per_step: device time of the collectives between chips
(all-gather, all-reduce, reduce-scatter, collective-permute, all-to-all)
per step, averaged over the cell's chips."""

PATTERN = (r"all-gather|all-reduce|reduce-scatter|collective-permute"
           r"|all-to-all")


def read(ctx):
    seconds = ctx.op_seconds(PATTERN)
    if seconds <= 0:
        return None
    return 1e3 * seconds / ctx.steps
