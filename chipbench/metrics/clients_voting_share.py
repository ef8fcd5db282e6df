"""clients.voting_share: the share of the voter forward/backward passes
the window computed whose vote counted: sum of the step's
``votes_cast`` over sum of its ``voter_passes``.  Below 100% the client
loop computes passes that absent clients then throw away."""
from metrics import _stages


def read(ctx):
    counts = _stages.of(ctx)["counts"]
    if not counts or counts[0] <= 0:
        return None
    passes, cast = counts
    return 100.0 * cast / passes
