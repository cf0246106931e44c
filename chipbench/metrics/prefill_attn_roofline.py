"""The Pallas prefill attention kernel's share of its roofline: the least
time of the served work's operations and bytes at the chip's peaks
(``costs.prefill_attention`` at the true prompt length and the published
head counts), over the kernel's device time, both per kernel call (one per
layer of each prefill).  Padding, of heads or of the length to a bucket,
is the program's and shows as lost share."""

from chipbench import costs

KERNEL = "prefill_flash"


def read(ctx):
    calls = [c for c in ctx.calls if c.kind == "prefill"]
    kernel_s, n = ctx.trace.op_seconds(KERNEL)
    if not calls or n == 0:
        return None
    d = ctx.dims
    least = sum(costs.least_seconds(
        *costs.prefill_attention(c.length, d.n_heads, d.n_kv_heads, d.head_dim,
                                 d.dtype_bytes),
        ctx.peak)[0] for c in calls) / len(calls)
    return 100.0 * least / (kernel_s / n)
