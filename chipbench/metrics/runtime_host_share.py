"""Share of the window's wall time the host spent outside the engines'
calls (``step``, ``prefill``, ``insert``): the runtime's event loop, the
tracker, allotment, and the prefill replica's modeled chunk ticks."""


def read(ctx):
    if not ctx.calls:
        return None
    inside = sum(c.t1 - c.t0 for c in ctx.calls)
    return 100.0 * (1.0 - inside / ctx.window_s)
