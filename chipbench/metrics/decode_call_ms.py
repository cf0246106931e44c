"""Host time per ``DecodeEngine.step``, which includes the logits pulled to
the host and the argmax there: the mean over every step of the window."""


def read(ctx):
    steps = [c.t1 - c.t0 for c in ctx.calls if c.kind == "step"]
    return 1e3 * sum(steps) / len(steps) if steps else None
