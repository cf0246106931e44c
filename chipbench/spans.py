"""The program's spans (``repro.obs.Span``) of a window, for the readers
of the metrics that read them.

A traced run whose ``Cluster`` carries an ``obs.Tracer`` has the window's
spans in ``ctx.spans``, on ``time.perf_counter_ns``, and the profiler's copy
of each span (its ``repro.<name>`` annotation) in ``ctx.trace.events
["spans"]`` as ``[name, start, duration]`` on the trace's clock.  A run with
neither has nothing for these readers, and each returns None.
"""

from __future__ import annotations

from chipbench import stats, xtrace


def closed(ctx, names) -> list:
    """The window's closed spans named in ``names``."""
    return [s for s in getattr(ctx, "spans", None) or ()
            if s.name in names and s.t1_ns is not None]


def p90_ms(seconds) -> float | None:
    return 1e3 * stats.percentile(seconds, 90) if seconds else None


def waits(ctx, names, last: str) -> list:
    """Seconds per request: the sum of its spans in ``names``, for every
    request whose ``last`` span closed inside the window."""
    total, ended = {}, set()
    for s in closed(ctx, names):
        total[s.rid] = total.get(s.rid, 0.0) + s.seconds
        if s.name == last:
            ended.add(s.rid)
    return [total[r] for r in sorted(ended)]


def annotated(ctx, names) -> list:
    """The union of the profiler's intervals of the spans in ``names``
    inside the traced stretch, as ``[start, end]`` on the trace's clock."""
    tr = ctx.trace
    return xtrace._union([max(s, tr.t0), min(s + d, tr.t1)]
                         for n, s, d in tr.events.get("spans", ())
                         if n in names and s + d > tr.t0 and s < tr.t1)


def overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
