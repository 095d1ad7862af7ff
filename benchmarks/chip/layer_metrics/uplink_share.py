"""uplink_share: the device time of the ops under the `round.uplink` scope
(Step 3: the uplink quantize and dequantize) as a share of all leaf-op
time on all chips, in %. On the ring impl the uplink runs inside the
collective and reads under `round.a2_average`. Read only where some op
runs under a stage scope (`stagetrace.share`). Moves rounds_per_s."""
from benchmarks.chip import stagetrace


def read(ctx):
    return stagetrace.share(ctx, "round.uplink")
