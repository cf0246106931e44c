"""The decode step's share of the chip's peak: the least time its required
operations and bytes take at the chip's peaks (weights, the live cache up to
each slot's position and the written position: ``costs.decode_step``), over
the device time of the programs each ``DecodeEngine.step`` runs, both per
step.  At these shapes the bound is the bandwidth."""

from chipbench import costs


def read(ctx):
    steps = [c for c in ctx.calls if c.kind == "step"]
    n, device_s = ctx.trace.count("step"), ctx.trace.module_seconds("step")
    if not steps or n == 0 or device_s == 0:
        return None
    least = sum(costs.least_seconds(*costs.decode_step(ctx.dims, c.positions),
                                        ctx.peak)[0] for c in steps)
    return 100.0 * (least / len(steps)) / (device_s / n)
