"""a2_collective_ms: device time of the collective ops per round, in ms,
on the chip where it is longest: the Algorithm-2 all-gather of the
workers' uploads (and any other collective the round runs). Read only
where the trace shows a collective. Moves rounds_per_s."""


def read(ctx):
    worst = max((d.collective_ns for d in ctx.devices), default=0.0)
    if ctx.rounds <= 0 or worst <= 0:
        return None
    return worst * 1e-6 / ctx.rounds
