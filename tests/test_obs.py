"""Observability plane: tracer-off bitwise identity, trace completeness,
serve-trace migration, metrics rollup and the Perfetto/JSONL exporters.

The obs plane's contract is *observation without interference*: attaching a
``Tracer`` must not move a single scheduling decision (the tracer-off path
is one attribute load + branch per emit site), and the event log must be
complete enough to reconstruct every grain's life (each dispatched grain
ends in exactly one complete or abort).  These tests pin both halves:

  - seeded property sweep: random fleets x faults x K shards, run traced
    and untraced, full ``RuntimeResult`` fingerprints compared exactly,
  - trace completeness under kill/steal/migration scenarios,
  - ``serve_stream``'s per-request traces are byte-identical whether the
    caller traces or not (satellite of the ad-hoc-trace migration: the
    tracer events are now the *only* carrier for TTFT/completion),
  - ``MetricsRegistry`` snapshot determinism + percentile arithmetic,
  - Perfetto ``trace_event`` structure: per-worker tracks, duration slices,
    migration flow-event pairs; JSONL round-trip,
  - spans: parents, inherited workers, request open/close pairs, their
    profiler annotations, and their export (the serving path's spans are
    tested through ``Cluster.serve`` in ``test_serve.py``).

Offline constraint: deterministic seeded sweeps (no hypothesis).
"""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest
from stub_engine import StubEngine, mk_requests

from repro.cluster import Cluster, SimJob
from repro.coord import CoordSpec, ShardedCoordinator
from repro.core import (
    AsyncRuntime, PerformanceTracker, PerfReport, SimWorker, TimelineEvent,
)
from repro.obs import (
    EVENT_KINDS, NO_SPAN, EventTracer, MetricsRegistry, Tracer, to_perfetto,
)
from repro.serve import FleetServer, Replica

DYADIC_COSTS = (0.25, 0.5, 1.0, 2.0, 4.0)
DYADIC_PERFS = (0.5, 1.0, 1.5, 2.0, 4.0)


def _fingerprint(res) -> tuple:
    """Everything a RunReport is built from, exact (no rounding)."""
    return (
        res.makespan,
        res.end_s,
        tuple(sorted(res.executed_by.items())),
        tuple((r.grain, r.worker, r.start_s, r.end_s, r.cost)
              for r in res.records),
        res.n_replans,
        res.n_migrated,
        res.n_steals,
        tuple(sorted(res.worker_finish.items())),
        tuple(sorted(res.worker_busy.items())),
    )


def _random_job(seed: int, tracer: Tracer | None):
    """One randomized fleet + timeline + (maybe) open-loop arrivals — the
    same generator the eta-mode bitwise sweep uses, with a tracer seam."""
    rng = np.random.default_rng(seed)
    n_workers = int(rng.integers(3, 9))
    n_grains = int(rng.integers(40, 160))
    k = int(rng.choice([1, 2, 3]))
    perfs = rng.choice(DYADIC_PERFS, size=n_workers)
    workers = [SimWorker(f"w{i}", float(p)) for i, p in enumerate(perfs)]
    tracker = PerformanceTracker(alpha=0.5, dead_after_s=1e18)
    for w in workers:
        tracker.observe(PerfReport(w.name, w.perf, 1.0, 0.0))
    authority = ShardedCoordinator(CoordSpec(k)) if k > 1 else None
    rt = AsyncRuntime(workers, tracker=tracker, authority=authority,
                      tracer=tracer)

    costs = rng.choice(DYADIC_COSTS, size=n_grains)
    uniform = bool(rng.integers(0, 2))
    cost_of = 1.0 if uniform else (lambda g: float(costs[g]))

    events = [TimelineEvent(3.0, "perf", "w0", float(perfs[0]) / 2)]
    if n_workers > 3 and rng.integers(0, 2):
        events.append(TimelineEvent(5.0, "kill", f"w{n_workers - 1}"))
        events.append(
            TimelineEvent(9.0, "join", SimWorker("wj", 2.0), 2.0))
    if k > 1 and rng.integers(0, 2):
        events.append(TimelineEvent(4.0, "ckill", 0))

    arrivals = None
    max_depth = None
    if rng.integers(0, 2):
        arrivals = np.sort(rng.exponential(0.4, size=n_grains)).tolist()
        if rng.integers(0, 2):
            max_depth = int(rng.integers(2, 6))
    res = rt.run(
        n_grains, grain_cost=cost_of, timeline=tuple(events),
        arrivals=arrivals, max_queue_depth=max_depth,
    )
    return res


# ---------------------------------------------------- tracer-off == traced
@pytest.mark.parametrize("seed", range(12))
def test_traced_run_bitwise_identical_to_untraced(seed):
    """Random fleets x faults x K: a tracer observes, never decides."""
    a = _random_job(seed, None)
    b = _random_job(seed, Tracer())
    assert _fingerprint(a) == _fingerprint(b)


@pytest.mark.parametrize("seed", range(12))
def test_trace_completeness_every_dispatch_resolves(seed):
    """Each dispatched grain's last lifecycle event is one complete or
    abort; completed grains match the result's executed_by exactly."""
    tracer = Tracer()
    res = _random_job(seed, tracer)
    assert {e.kind for e in tracer.events} <= EVENT_KINDS
    dispatched: set[int] = set()
    open_grains: set[int] = set()
    completed: dict[int, str] = {}
    for e in tracer.events:
        if e.kind == "dispatch":
            dispatched.add(e.grain)
            open_grains.add(e.grain)
        elif e.kind == "complete":
            assert e.grain in open_grains, "complete without dispatch"
            open_grains.discard(e.grain)
            completed[e.grain] = e.worker
        elif e.kind == "abort":
            assert e.grain in open_grains, "abort without dispatch"
            open_grains.discard(e.grain)
    assert not open_grains, f"grains dispatched but never resolved: {open_grains}"
    assert completed == res.executed_by
    # Shed grains never dispatch; everything else completes exactly once.
    assert len(completed) == len(res.records)


def test_trace_completeness_under_kill():
    """A killed worker's in-flight grains abort, then re-dispatch and
    complete on a survivor — visible end-to-end in the event log."""
    workers = [SimWorker("a", 2.0), SimWorker("b", 1.0)]
    tracker = PerformanceTracker(alpha=0.5, dead_after_s=1e18)
    for w in workers:
        tracker.observe(PerfReport(w.name, w.perf, 1.0, 0.0))
    tracer = Tracer()
    rt = AsyncRuntime(workers, tracker=tracker, tracer=tracer)
    res = rt.run(24, timeline=(TimelineEvent(2.0, "kill", "a"),))
    aborted = [e.grain for e in tracer.events if e.kind == "abort"]
    assert aborted, "the kill aborted nothing in flight"
    for g in aborted:
        later = [e.kind for e in tracer.events
                 if e.grain == g and e.kind in ("dispatch", "complete")]
        assert later.count("complete") == 1, (g, later)
        # The retry landed on the survivor (grains done before the kill
        # stay attributed to "a" — only aborted work must move).
        assert res.executed_by[g] == "b"


# ------------------------------------------------ serve_stream trace parity
def _stream_report(tracer):
    server = FleetServer(
        [Replica("r0", 4.0), Replica("r1", 2.0)],
        {"r0": StubEngine(max_batch=2, name="r0"),
         "r1": StubEngine(max_batch=2, name="r1")},
        max_queue_depth=8, tracer=tracer,
    )
    reqs = mk_requests(10, max_new=4)
    return server.serve_stream(reqs, [0.5 * i for i in range(10)])


def test_serve_stream_traces_identical_with_and_without_tracer():
    """Per-request TTFT/completion now ride the Tracer event vocabulary;
    the visible RequestTraces and LatencyStats must not move a byte."""
    rep0 = _stream_report(None)
    rep1 = _stream_report(Tracer())
    assert rep0.traces == rep1.traces
    assert rep0.latency == rep1.latency
    assert rep0.sim_time_s == rep1.sim_time_s


def test_serve_stream_emits_serve_events():
    tracer = Tracer()
    rep = _stream_report(tracer)
    kinds = {e.kind for e in tracer.events}
    assert {"arrive", "admit", "dispatch", "first_token",
            "request_done", "complete"} <= kinds
    fts = [e for e in tracer.events if e.kind == "first_token"]
    assert len(fts) == rep.n_served
    # The folded trace values came from these exact events.
    for e in fts:
        assert rep.traces[e.grain].first_token_s == e.t_s
    # The tracer derives TTFT by pairing first_token with arrive, so the
    # telemetry histogram agrees with the folded LatencyStats.
    h = tracer.telemetry()["histograms"]["ttft_s"]
    assert h["count"] == rep.n_served
    assert h["mean"] == pytest.approx(rep.latency.mean_ttft_s)


def test_heartbeats_populate_rate_gauges():
    tracer = Tracer()
    _random_job(0, tracer)
    gauges = tracer.telemetry()["gauges"]
    rates = {k: v for k, v in gauges.items() if k.startswith("rate.")}
    assert rates, "no per-worker rate gauges from heartbeats"
    assert all(v > 0 for v in rates.values())


# ------------------------------------------------------------------ metrics
def test_metrics_registry_snapshot_deterministic_order():
    m = MetricsRegistry()
    for name in ("z", "a", "m"):
        m.count(name, 2)
        m.gauge(name, 1.5)
    for v in (4.0, 1.0, 3.0, 2.0):
        m.observe("lat", v)
    snap = m.snapshot()
    assert list(snap["counters"]) == ["a", "m", "z"]
    assert list(snap["gauges"]) == ["a", "m", "z"]
    h = snap["histograms"]["lat"]
    assert (h["count"], h["sum"], h["min"], h["max"]) == (4, 10.0, 1.0, 4.0)
    assert h["mean"] == 2.5
    assert h["p50"] == 2.5          # linear interpolation on 4 samples
    assert h["p99"] == pytest.approx(3.97)
    # Same inputs, same snapshot — byte-stable for RunReport.telemetry.
    assert json.dumps(snap, sort_keys=False) == json.dumps(m.snapshot())


def test_tracer_metrics_rollup_and_summary_line():
    lines = []
    tracer = Tracer(metrics_interval_s=1.0, log_fn=lines.append)
    tracer.emit("dispatch", t_s=0.1, worker="w0", grain=0)
    tracer.emit("complete", t_s=0.9, worker="w0", grain=0, start_s=0.1)
    tracer.emit("migrate", t_s=1.2, worker="w0", grain=1, to="w1")
    tracer.emit("complete", t_s=3.5, worker="w1", grain=1, start_s=1.2)
    snap = tracer.telemetry()
    assert snap["counters"]["events.complete"] == 2
    assert snap["counters"]["grains_moved"] == 1
    assert snap["histograms"]["grain_service_s"]["count"] == 2
    assert snap["n_events"] == 4
    # Interval crossings at t=1.2 and t=3.5 (one line per crossing, the
    # 2.x boundary is skipped, not back-filled).
    assert len(lines) == 2
    assert all("complete=" in ln for ln in lines)


def test_cluster_trace_flag_builds_and_validates():
    c = Cluster("2:1", trace=True)
    assert isinstance(c.tracer, Tracer)
    rep = c.simulate(SimJob(size=16))
    assert rep.telemetry["n_events"] == len(c.tracer.events) > 0
    with pytest.raises(TypeError):
        Cluster("2:1", trace="yes")
    assert Cluster("2:1").simulate(SimJob(size=16)).telemetry is None


# ---------------------------------------------------------------- exporters
def _traced_halve_run():
    tracer = Tracer()
    cluster = Cluster("fast=4,mid=2,slow=1", trace=tracer)
    cluster.simulate(SimJob(size=96), scenario="halve:fast@25%")
    return tracer


def test_perfetto_export_structure_and_flows():
    tracer = _traced_halve_run()
    doc = to_perfetto(tracer.events)
    evs = doc["traceEvents"]
    tracks = {e["tid"]: e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"coordinator", "fast", "mid", "slow"} <= set(tracks.values())
    # Every record carries the trace_event schema fields.
    assert all({"ph", "ts", "pid", "tid", "name"} <= set(e) for e in evs)
    slices = [e for e in evs if e["ph"] == "X"]
    assert slices and all(e["dur"] >= 0 for e in slices)
    n_complete = sum(1 for e in tracer.events if e.kind == "complete")
    assert len(slices) == n_complete
    # The halved worker sheds load: migration flow pairs leave its track.
    starts = [e for e in evs if e["ph"] == "s"]
    finishes = {e["id"]: e for e in evs if e["ph"] == "f"}
    assert starts, "no migration flow events under a halve scenario"
    fast_tid = next(t for t, n in tracks.items() if n == "fast")
    assert any(e["tid"] == fast_tid for e in starts)
    for s in starts:
        f = finishes.get(s["id"])
        assert f is not None and f["ts"] >= s["ts"] - 1e-9
        assert f["tid"] != s["tid"], "flow must land on another track"


def test_jsonl_export_roundtrip(tmp_path):
    tracer = _traced_halve_run()
    path = tmp_path / "trace.jsonl"
    n = tracer.export(str(path))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert len(lines) == n == len(tracer.events)
    for rec, e in zip(lines, tracer.events):
        assert rec["kind"] == e.kind
        assert rec["t_s"] == e.t_s
        assert rec["worker"] == e.worker


def test_perfetto_export_writes_loadable_json(tmp_path):
    tracer = _traced_halve_run()
    path = tmp_path / "trace.json"
    n = tracer.export(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    assert len([e for e in doc["traceEvents"] if e["ph"] != "M"]) >= n


# -------------------------------------------------------------------- spans
def _spanned_tracer():
    tracer = Tracer()
    tracer.open("request.queue", 7)
    with tracer.span("runtime.tick", worker="a") as tick:
        with tracer.span("engine.step", n=1) as step:
            step.set(active=2)
        tick.set(done=True)
    tracer.close("request.queue", 7, worker="a")
    tracer.close("request.queue", 7)         # not open any more: nothing
    tracer.close("request.handoff", 3)       # never opened: nothing
    tracer.open("request.handoff", 8)        # left open
    return tracer


def test_span_records_parent_worker_rid_and_attrs():
    tracer = _spanned_tracer()
    queue, tick, step, handoff = tracer.spans
    assert [s.name for s in tracer.spans] == [
        "request.queue", "runtime.tick", "engine.step", "request.handoff"]
    assert (tick.parent, step.parent, queue.parent) == (None, tick.id, None)
    assert step.worker == "a"                # inherited from its parent
    assert step.attrs == {"n": 1, "active": 2} and tick.attrs == {"done": True}
    assert (queue.rid, queue.worker, queue.keyed) == (7, "a", True)
    assert not tick.keyed
    assert queue.t0_ns <= tick.t0_ns <= step.t0_ns <= step.t1_ns \
        <= tick.t1_ns <= queue.t1_ns
    assert handoff.t1_ns is None
    assert tracer._stack == []


def test_span_annotations_enter_the_profiler(monkeypatch):
    entered, exited = [], []

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            exited.append(self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    _spanned_tracer()
    assert entered == ["repro.request.queue", "repro.runtime.tick",
                       "repro.engine.step", "repro.request.handoff"]
    assert exited == ["repro.engine.step", "repro.runtime.tick",
                      "repro.request.queue"]


def test_event_tracer_and_no_span_record_nothing():
    tracer = EventTracer()
    with tracer.span("engine.step", worker="a") as sp:
        sp.set(active=1)
    tracer.open("request.queue", 1)
    tracer.close("request.queue", 1)
    tracer.emit("arrive", t_s=0.0, grain=0)
    assert tracer.spans == [] and len(tracer.events) == 1
    with NO_SPAN("engine.step", worker="a") as sp:
        sp.set(active=1)
    assert NO_SPAN("x") is NO_SPAN


def test_perfetto_export_writes_spans_on_the_wall_clock():
    tracer = _spanned_tracer()
    doc = to_perfetto(tracer.events, tracer.spans, origin_ns=0)
    recs = [e for e in doc["traceEvents"] if e["pid"] == 2]
    names = {e["args"]["name"] for e in recs if e["name"] == "thread_name"}
    assert names == {"fleet", "a"}
    slices = {e["name"]: e for e in recs if e["ph"] == "X"}
    assert set(slices) == {"runtime.tick", "engine.step"}
    step = tracer.spans[2]
    assert slices["engine.step"]["ts"] == step.t0_ns / 1e3
    assert slices["engine.step"]["dur"] == (step.t1_ns - step.t0_ns) / 1e3
    assert slices["engine.step"]["args"]["active"] == 2
    # The request's wait is an async pair; the span left open is not written.
    pair = [e for e in recs if e["ph"] in ("b", "e")]
    assert [(e["ph"], e["name"], e["id"]) for e in pair] == [
        ("b", "request.queue", 7), ("e", "request.queue", 7)]


def test_export_counts_events_and_closed_spans(tmp_path):
    tracer = _spanned_tracer()
    tracer.emit("arrive", t_s=0.0, grain=0)
    n = tracer.export(str(tmp_path / "t.jsonl"))
    lines = [json.loads(ln) for ln in (tmp_path / "t.jsonl").read_text().splitlines()]
    assert n == len(lines) == 4
    assert lines[0]["kind"] == "arrive"
    assert [ln["span"] for ln in lines[1:]] == [
        "request.queue", "runtime.tick", "engine.step"]
    assert lines[3]["parent"] == lines[2]["id"] and lines[3]["active"] == 2
    assert tracer.export(str(tmp_path / "t.json")) == 4
