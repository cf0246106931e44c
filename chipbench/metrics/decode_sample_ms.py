"""Host time per decode step spent pulling the logits and taking the argmax
on the host: the ``engine.step.fetch`` and ``engine.step.sample`` spans,
summed over the window and divided by its ``engine.step`` spans that ran
the decode program.  Whole window."""

from chipbench import spans


def read(ctx):
    steps = {s.id for s in spans.closed(ctx, ("engine.step",)) if s.attrs["active"]}
    if not steps:
        return None
    host = sum(s.seconds for s in spans.closed(ctx, ("engine.step.fetch",
                                                     "engine.step.sample"))
               if s.parent in steps)
    return 1e3 * host / len(steps)
