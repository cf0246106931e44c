"""p90 over the window's requests of the time from the pool's submission to
the request's decode slot or the start of its prefill: its
``request.backlog`` span (waiting for its wave) plus its ``request.queue``
span (waiting, after its wave's dispatch, for an engine).  Whole window."""

from chipbench import spans


def read(ctx):
    return spans.p90_ms(spans.waits(ctx, ("request.backlog", "request.queue"),
                                    last="request.queue"))
