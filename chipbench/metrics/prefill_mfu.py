"""Model operations of the prefills at their true prompt lengths
(``costs.prefill``), over the device time of the programs each
``DecodeEngine.prefill`` runs times the chip's peak rate, both per prefill."""

from chipbench import costs


def read(ctx):
    calls = [c for c in ctx.calls if c.kind == "prefill"]
    n, device_s = ctx.trace.count("prefill"), ctx.trace.module_seconds("prefill")
    if not calls or n == 0 or device_s == 0:
        return None
    flops = sum(costs.prefill(ctx.dims, c.length) for c in calls) / len(calls)
    return 100.0 * flops / (device_s / n * ctx.peak["flops_per_s"])
