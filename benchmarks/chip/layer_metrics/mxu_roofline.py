"""mxu_roofline: the required FLOPs of the round over the device time
of every convolution and dot op in it, as a share of the bf16 peak,
in %.

The denominator sums the device time of all ops the trace files as a
convolution or a dot, on every chip, per round. Counting dots as well
as convolutions keeps a change that turns a convolution into a matmul
from leaving the time out. Moves rounds_per_s."""


def read(ctx):
    mxu_s = sum(d.mxu_ns for d in ctx.devices) * 1e-9
    if ctx.rounds <= 0 or mxu_s <= 0:
        return None
    return 100.0 * ctx.flops["total"] * ctx.rounds / (
        mxu_s * ctx.peaks["bf16_flops"])
