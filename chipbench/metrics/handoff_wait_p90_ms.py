"""p90 over the window's disaggregated requests of the ``request.handoff``
span: from the prefill's ``KVHandoff`` to the decode engine's ``insert``.
Whole window."""

from chipbench import spans


def read(ctx):
    return spans.p90_ms(spans.waits(ctx, ("request.handoff",),
                                    last="request.handoff"))
