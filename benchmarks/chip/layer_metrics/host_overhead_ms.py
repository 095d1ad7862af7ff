"""host_overhead_ms: the fused driver's host time per round, in ms: the
sum over the traced `trainer.dispatch` spans of each span less its
`trainer.wait` children (enqueue, read-back and records), over the
rounds of the window. Read only where the trace holds the program's
dispatch spans and the context its host events. Moves rounds_per_s."""
from benchmarks.chip import stagetrace


def read(ctx):
    host = getattr(ctx, "host", None)
    return None if host is None else stagetrace.host_overhead_ms(
        host, ctx.rounds)
