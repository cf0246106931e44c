"""Host time per ``DecodeEngine.prefill``: the mean over every prefill of
the window."""


def read(ctx):
    calls = [c.t1 - c.t0 for c in ctx.calls if c.kind == "prefill"]
    return 1e3 * sum(calls) / len(calls) if calls else None
