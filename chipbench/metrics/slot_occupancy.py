"""Share of the decode slots that held a request: the sum of ``active``
over the sum of ``max_batch``, over every ``engine.step`` span of the
window.  Whole window."""

from chipbench import spans


def read(ctx):
    steps = spans.closed(ctx, ("engine.step",))
    slots = sum(s.attrs["max_batch"] for s in steps)
    if not slots:
        return None
    return 100.0 * sum(s.attrs["active"] for s in steps) / slots
