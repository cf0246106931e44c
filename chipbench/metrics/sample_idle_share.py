"""Share of the traced stretch in which no operation ran on the device
while the host was inside ``engine.step.fetch`` or ``engine.step.sample``
(their ``repro.*`` annotations on the profiler's host plane): the device
time that sampling on the host leaves idle."""

from chipbench import spans

NAMES = ("repro.engine.step.fetch", "repro.engine.step.sample")


def read(ctx):
    tr = ctx.trace
    host = spans.annotated(ctx, NAMES)
    if not host or not tr.events["ops"]:
        return None
    inside = sum(e - s for s, e in host)
    return 100.0 * (inside - spans.overlap(host, tr.busy)) / (tr.t1 - tr.t0)
