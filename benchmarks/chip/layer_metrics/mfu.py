"""mfu: the whole round's share of the chips' bf16 peak, in %.

Required FLOPs per round (flops/<family>.py, from the shapes) times the
rounds completed per second of the traced window (host clock, whole
dispatches ending in block_until_ready), over chips x peak. Moves
rounds_per_s."""


def read(ctx):
    if ctx.rounds <= 0 or ctx.window_s <= 0:
        return None
    rate = ctx.flops["total"] * ctx.rounds / ctx.window_s
    return 100.0 * rate / (ctx.chips * ctx.peaks["bf16_flops"])
