"""a3_server_share: the device time of the ops under the `round.a3_server`
scope (Algorithm 3: the server's generator update) as a share of all
leaf-op time on all chips, in %. Read only where some op runs under a
stage scope (`stagetrace.share`). Moves rounds_per_s."""
from benchmarks.chip import stagetrace


def read(ctx):
    return stagetrace.share(ctx, "round.a3_server")
