"""prologue.boundary_ms: what a round boundary adds to a step (the cloud
mean over edges and the anchor pass): median device time of the
boundary steps' programs minus that of the local steps'."""
import statistics


def read(ctx):
    bnd, loc = ctx.boundary_split()
    if not bnd or not loc:
        return None
    return 1e3 * (statistics.median(bnd) - statistics.median(loc))
