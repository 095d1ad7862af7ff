"""a2_average_share: the device time of the ops under the
`round.a2_average` scope (Algorithm 2: the all-gather, the `wavg`,
robust or ring kernel and the no-survivor fallback) as a share of all
leaf-op time on all chips, in %. Read only where some op runs under a
stage scope (`stagetrace.share`). Moves rounds_per_s."""
from benchmarks.chip import stagetrace


def read(ctx):
    return stagetrace.share(ctx, "round.a2_average")
