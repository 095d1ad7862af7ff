"""device_idle_share: the share of the traced window in which no op ran
on the device, in %, on the chip with the most idle time. Moves
rounds_per_s."""


def read(ctx):
    shares = [1.0 - d.busy_ns * 1e-9 / d.window_s for d in ctx.devices
              if d.window_s > 0]
    return 100.0 * max(shares) if shares else None
