"""Structured run tracing: typed lifecycle events with dual timestamps.

One ``Tracer`` instance observes one run (or several back-to-back runs on
the same runtime).  Every layer that can see it emits typed events through
``emit(kind, ...)``:

  grain lifecycle   enqueue / dispatch / start / heartbeat / migrate /
                    steal / abort / complete
  serve pool        arrive / admit / shed / handoff / first_token /
                    ttft_drop / request_done
  coordinator       rebalance / cross_steal / ckill / gossip
  scenario          fault
  backend           settle (wallclock measurement reconciliation)

Each event carries the *logical* clock (``t_s`` — simulated seconds under
``SimBackend``, measured seconds under ``WallclockBackend``, so both
backends trace identically) and a *wall* timestamp (``wall_s`` — real
seconds since the tracer was created), plus an optional worker, grain id,
and a free-form data dict.

The emitting layers guard every call site with ``if tracer is not None:``
— the no-tracer path loads one attribute and branches, nothing else, which
is what keeps it bitwise-identical and within noise on ``bench_loop``
(asserted there and in ``tests/test_obs.py``).

The logical clock is *injected*: the runtime calls ``set_clock`` with its
job-context clock at job start, so emit sites that have no ``now`` in scope
(rebalance moves, steals, gossip rounds) still stamp correctly.  Call sites
that do have ``now`` pass it explicitly via ``t_s=``.

Metrics roll up as events arrive (one counter per kind; service-time and
TTFT histograms; per-worker ``rate.<w>`` gauges from heartbeats — TTFT is
derived inside the tracer by pairing each ``first_token`` with its grain's
``arrive``, since the emitting executor never sees arrival times) into a
``MetricsRegistry``
whose ``snapshot()`` becomes ``RunReport.telemetry``.  With
``metrics_interval_s`` set, the tracer prints a one-line stat summary every
time the logical clock crosses the next interval boundary — the live-run
heartbeat for long open-loop streams.

Beside its events a tracer records *spans*: named stretches of host time on
``time.perf_counter_ns``, each with its parent span, worker, request id
(``rid``) and a free-form attrs dict.  Two forms:

  ``with tracer.span(name, worker=..., rid=..., **attrs) as sp:``
      a stretch of one call; spans opened inside it are its children, and a
      child without a worker inherits its parent's.  ``sp.set(**attrs)``
      adds counters before it closes.
  ``tracer.open(name, rid)`` ... ``tracer.close(name, rid, worker=...)``
      a request's wait that begins in one call and ends in another, keyed by
      ``(name, rid)``; closing a span that is not open does nothing.

While a span is open it is also a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``, so a profiler trace taken meanwhile holds every span on
its host plane, on the same clock as the device's operations.  Spans stay in
memory (``Tracer.spans``) until ``export`` writes them.  Span sites follow
the emit-site contract: with no tracer they take ``NO_SPAN``, a shared
do-nothing stand-in, so no span is built and no annotation entered.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

from .metrics import MetricsRegistry

__all__ = ["EVENT_KINDS", "EventTracer", "NO_SPAN", "Span", "TraceEvent",
           "Tracer"]

#: The closed event vocabulary (exporters render anything, but tests assert
#: emitting layers stay inside it).
EVENT_KINDS = frozenset({
    # grain lifecycle
    "enqueue", "dispatch", "start", "heartbeat", "migrate", "steal",
    "abort", "complete",
    # serve pool
    "arrive", "admit", "shed", "handoff", "first_token", "ttft_drop",
    "request_done",
    # coordinator
    "rebalance", "cross_steal", "ckill", "gossip",
    # scenario + backend
    "fault", "settle",
})


@dataclasses.dataclass(slots=True)
class TraceEvent:
    kind: str                  # one of EVENT_KINDS
    t_s: float                 # logical clock (sim or measured seconds)
    wall_s: float              # real seconds since the tracer's creation
    worker: str | None         # track owner (None -> coordinator track)
    grain: int | None          # grain / request id when applicable
    data: dict[str, Any]       # kind-specific payload


#: Prefix of the profiler annotation each span enters: ``repro.<name>``.
SPAN_PREFIX = "repro."


@dataclasses.dataclass(slots=True)
class Span:
    name: str
    id: int                    # unique within its tracer
    parent: int | None         # id of the enclosing span (None: top level)
    worker: str | None         # track owner
    rid: int | None            # request id, shared by all spans of a request
    t0_ns: int                 # time.perf_counter_ns at open
    t1_ns: int | None          # ... at close (None while open)
    attrs: dict[str, Any]
    keyed: bool = False        # opened by ``open``, closed by ``close``

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


def _annotation(name: str):
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(SPAN_PREFIX + name)
    ann.__enter__()
    return ann


class _NoSpan:
    """What a span site uses when there is no tracer: ``NO_SPAN(name, ...)``
    returns itself, entering and ``set`` do nothing."""

    __slots__ = ()

    def __call__(self, name: str, **attrs: Any) -> "_NoSpan":
        return self

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **attrs: Any) -> None:
        pass


NO_SPAN = _NoSpan()


class _Scope:
    """One ``Tracer.span`` while it is open."""

    __slots__ = ("_tracer", "_span", "_ann")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span

    def __enter__(self) -> "_Scope":
        tr, sp = self._tracer, self._span
        stack = tr._stack
        if stack:
            sp.parent = stack[-1].id
            if sp.worker is None:
                sp.worker = stack[-1].worker
        self._ann = _annotation(sp.name)
        sp.t0_ns = time.perf_counter_ns()
        tr.spans.append(sp)
        stack.append(sp)
        return self

    def __exit__(self, *exc) -> None:
        self._span.t1_ns = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        self._tracer._stack.pop()

    def set(self, **attrs: Any) -> None:
        self._span.attrs.update(attrs)


class Tracer:
    """Collects ``TraceEvent``s and rolls them into a ``MetricsRegistry``;
    records ``Span``s (module docstring).

    Parameters:
      metrics_interval_s  print a one-line summary every S logical seconds
                          (None: silent),
      log_fn              where interval summaries go (default ``print``).
    """

    def __init__(self, metrics_interval_s: float | None = None,
                 log_fn: Callable[[str], None] = print) -> None:
        self.events: list[TraceEvent] = []
        self.metrics = MetricsRegistry()
        self.metrics_interval_s = (
            float(metrics_interval_s) if metrics_interval_s else None
        )
        self.log_fn = log_fn
        self._origin = time.perf_counter()
        self._clock: Callable[[], float] = lambda: 0.0
        # arrive-time per grain, so first_token events (emitted by executors
        # that never see arrival times) still yield a TTFT sample.
        self._arrive_s: dict[int, float] = {}
        self._next_report_s = (
            self.metrics_interval_s if self.metrics_interval_s else None
        )
        self.spans: list[Span] = []
        self._stack: list[Span] = []        # open ``span`` scopes, innermost last
        self._open: dict[tuple[str, int], tuple[Span, Any]] = {}
        self._next_span = 0

    # -- wiring ---------------------------------------------------------------
    def set_clock(self, clock: Callable[[], float]) -> None:
        """Inject the logical clock (the runtime's job-context ``clock``) so
        emit sites without a ``now`` in scope stamp correctly."""
        self._clock = clock

    # -- the hot entry point (only reached when tracing is ON) ----------------
    def emit(self, kind: str, *, t_s: float | None = None,
             worker: str | None = None, grain: int | None = None,
             **data: Any) -> None:
        t = self._clock() if t_s is None else t_s
        self.events.append(TraceEvent(
            kind, t, time.perf_counter() - self._origin, worker, grain, data,
        ))
        m = self.metrics
        m.count("events." + kind)
        if kind == "complete":
            start = data.get("start_s")
            if start is not None:
                m.observe("grain_service_s", t - start)
        elif kind == "first_token":
            ttft = data.get("ttft_s")
            if ttft is None and grain in self._arrive_s:
                ttft = t - self._arrive_s[grain]
            if ttft is not None:
                m.observe("ttft_s", ttft)
        elif kind == "arrive" and grain is not None:
            self._arrive_s[grain] = t
        elif kind == "heartbeat" and worker is not None:
            el = data.get("elapsed_s")
            if el:
                m.gauge("rate." + worker, data.get("work", 0.0) / el)
        elif kind == "migrate" or kind == "steal":
            m.count("grains_moved")
        if self._next_report_s is not None and t >= self._next_report_s:
            # One line per crossed boundary, not per missed interval.
            interval = self.metrics_interval_s
            self._next_report_s += (
                int((t - self._next_report_s) / interval) + 1
            ) * interval
            self.log_fn(self.summary_line(t))

    # -- spans (only reached when tracing is ON) --------------------------------
    def _new_span(self, name: str, worker: str | None, rid: int | None,
                  attrs: dict[str, Any]) -> Span:
        self._next_span += 1
        return Span(name, self._next_span, None, worker, rid, 0, None, attrs)

    def span(self, name: str, *, worker: str | None = None,
             rid: int | None = None, **attrs: Any) -> _Scope:
        """A span over the ``with`` block it opens (module docstring)."""
        return _Scope(self, self._new_span(name, worker, rid, attrs))

    def open(self, name: str, rid: int, **attrs: Any) -> None:
        """Begin request ``rid``'s span ``name``; ``close`` ends it."""
        sp = self._new_span(name, attrs.pop("worker", None), rid, attrs)
        sp.keyed = True
        ann = _annotation(name)
        sp.t0_ns = time.perf_counter_ns()
        self.spans.append(sp)
        self._open[(name, rid)] = (sp, ann)

    def close(self, name: str, rid: int, *, worker: str | None = None,
              **attrs: Any) -> None:
        """End request ``rid``'s open span ``name``, if any; ``worker``
        names where the wait ended."""
        entry = self._open.pop((name, rid), None)
        if entry is None:
            return
        sp, ann = entry
        sp.t1_ns = time.perf_counter_ns()
        ann.__exit__(None, None, None)
        if worker is not None:
            sp.worker = worker
        sp.attrs.update(attrs)

    # -- reporting ------------------------------------------------------------
    def summary_line(self, t_s: float | None = None) -> str:
        """One-line live stats: event totals for the kinds that tell the
        load-balancing story."""
        c = self.metrics.counters
        t = self._clock() if t_s is None else t_s
        parts = [f"[obs t={t:9.3f}s]", f"events={len(self.events)}"]
        for kind in ("complete", "migrate", "steal", "shed", "abort",
                     "gossip", "rebalance"):
            n = c.get("events." + kind, 0)
            if n:
                parts.append(f"{kind}={n}")
        return " ".join(parts)

    def telemetry(self) -> dict:
        """The ``RunReport.telemetry`` payload: metrics snapshot plus the raw
        event count (the events themselves live in the tracer / export
        files, not the report)."""
        snap = self.metrics.snapshot()
        snap["n_events"] = len(self.events)
        return snap

    def export(self, path: str) -> int:
        """Write the collected events and closed spans to ``path``:
        Perfetto/Chrome ``trace_event`` JSON, or compact JSONL when the path
        ends in ``.jsonl``.  Returns the number of records written."""
        from .export import write_trace
        return write_trace(self.events, path, spans=self.spans,
                           origin_ns=int(self._origin * 1e9))


class EventTracer(Tracer):
    """A ``Tracer`` that keeps events and no spans: the carrier the serving
    plane attaches for a stream's lifecycle events when the caller traces
    nothing, so an untraced stream builds no span."""

    def span(self, name: str, **attrs: Any) -> _NoSpan:
        return NO_SPAN

    def open(self, name: str, rid: int, **attrs: Any) -> None:
        pass

    def close(self, name: str, rid: int, **attrs: Any) -> None:
        pass
