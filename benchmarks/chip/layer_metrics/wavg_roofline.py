"""wavg_roofline: the Algorithm-2 `wavg` Pallas kernel's share of its
HBM roofline, in %.

The bytes one call needs (flops/<family>.py: the (K, N) uploads read,
the average written, the weights read; N unpadded) over the kernel's
mean device time per call, as a share of the chip's HBM bandwidth. The
kernel is memory bound: its FLOPs (2 K N) take far less time at the MXU
peak than its bytes at the HBM peak. Read only where the trace shows
the kernel. Moves rounds_per_s."""

KERNEL = "wavg"           # part of the kernel wrapper's name, `wavg_pallas`


def read(ctx):
    calls = sum(n for d in ctx.devices for k, n in d.kernel_calls.items()
                if KERNEL in k)
    total_s = sum(t for d in ctx.devices for k, t in d.kernel_ns.items()
                  if KERNEL in k) * 1e-9
    if calls == 0 or total_s <= 0:
        return None
    return 100.0 * ctx.wavg_bytes / (total_s / calls) / (
        ctx.peaks["hbm_bytes_per_s"])
