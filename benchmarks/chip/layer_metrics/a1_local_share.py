"""a1_local_share: the device time of the ops under the `round.a1_local`
scope (Algorithm 1: the workers' local discriminator steps with the fake
generator forwards they make) as a share of all leaf-op time on all
chips, in %. Read only where some op runs under a stage scope
(`stagetrace.share`). Moves rounds_per_s."""
from benchmarks.chip import stagetrace


def read(ctx):
    return stagetrace.share(ctx, "round.a1_local")
