"""Open-loop streaming serving: arrivals, admission control, latency SLOs,
and reactive autoscaling — at timing scale with stub engines.

Covers the full stack top-down:

  runtime   grains *arrive* (ArrivalSource): join-the-homogenized-shortest-
            queue admission, bounded per-replica depth, shed-or-backlog
            overflow, workload events rejected at the execution plane,
  fleet     serve_stream traces (enqueue/first-token/completion), shed
            records, LatencyStats percentiles, the metrics->membership loop
            (scale rules joining replicas on a measured p99 breach), and the
            per-replica wave-quota fix,
  scenario  workload-clause grammar (arrive/burst/mix/scale) round-trips,
            bitwise-deterministic seeded arrivals, phase-relative anchoring,
  cluster   the facade's open-loop route, pool sizing, mix shifts, and the
            actionable rejections (sim/train refuse workload scenarios;
            scale rules need an engine factory).
"""

import math

import pytest
from stub_engine import StubEngine, expected_tokens, mk_requests

from repro.cluster import (
    Cluster,
    FleetSpec,
    ScaleRule,
    Scenario,
    ServeJob,
    TrainJob,
    materialize_workload,
)
from repro.core import (
    ArrivalSource,
    AsyncRuntime,
    PerformanceTracker,
    PerfReport,
    SimWorker,
    TimelineEvent,
)
from repro.serve import FleetServer, Replica


def mk_runtime(perfs):
    workers = [SimWorker(f"w{i}", float(p)) for i, p in enumerate(perfs)]
    tracker = PerformanceTracker(alpha=0.5)
    for w in workers:
        tracker.observe(PerfReport(w.name, w.perf, 1.0, 0.0))
    return workers, AsyncRuntime(workers, tracker=tracker)


def mk_server(specs, **kw):
    """specs: list of (name, perf, max_batch)."""
    replicas = [Replica(n, p) for n, p, _ in specs]
    engines = {n: StubEngine(max_batch=b, name=n) for n, _, b in specs}
    return FleetServer(replicas, engines, **kw)


def stub_factory(spec):
    # Duck-typed over both factory seams: FleetServer passes a Replica
    # (no concurrency), Cluster passes a WorkerSpec.
    return StubEngine(max_batch=getattr(spec, "concurrency", 2),
                      max_seq=64, name=spec.name)


# ================================================================== runtime
def test_arrivals_complete_and_record_times():
    _, rt = mk_runtime([2.0, 1.0])
    res = rt.run(6, grain_cost=1.0, arrivals=[0.0, 0.5, 1.0, 1.5, 2.0, 2.5])
    assert len(res.values) == 6 and not res.shed
    assert res.arrive_s == {g: 0.5 * g for g in range(6)}
    # An arrival can never finish before it arrives.
    for rec in res.records:
        assert rec.end_s >= res.arrive_s[rec.grain]


def test_arrivals_favor_fast_worker():
    """Admission is join-the-homogenized-shortest-queue: with a 3x perf
    spread, the fast worker absorbs most of a simultaneous burst."""
    _, rt = mk_runtime([3.0, 1.0])
    res = rt.run(8, grain_cost=1.0, arrivals=[0.0] * 8)
    shares = res.shares()
    assert shares["w0"] > shares["w1"]


def test_backlog_drains_when_queues_free():
    """overflow='queue': arrivals beyond every queue's depth wait runtime-
    side and are admitted as slots free — nothing is lost."""
    _, rt = mk_runtime([1.0, 1.0])
    res = rt.run(12, grain_cost=1.0, arrivals=[0.0] * 12, max_queue_depth=2)
    assert len(res.values) == 12 and not res.shed


def test_shed_records_explicit_rejects():
    _, rt = mk_runtime([1.0])
    res = rt.run(8, grain_cost=4.0, arrivals=[0.0] * 8,
                 max_queue_depth=1, overflow="shed")
    assert res.shed, "a depth-1 queue under an 8-grain burst must shed"
    assert len(res.values) + len(res.shed) == 8
    # Shed grains still have their arrival recorded (the reject trace).
    for g in res.shed:
        assert g in res.arrive_s
        assert g not in res.values


def test_arrival_validation():
    _, rt = mk_runtime([1.0, 1.0])
    with pytest.raises(ValueError, match="initial_plan"):
        rt.run(2, arrivals=[0.0, 0.0], initial_plan=rt.plan(2))
    with pytest.raises(ValueError, match="overflow"):
        rt.run(2, arrivals=[0.0, 0.0], overflow="drop")
    with pytest.raises(ValueError, match="max_queue_depth"):
        rt.run(2, grain_cost=1.0, max_queue_depth=2)
    with pytest.raises(ValueError, match="covers 1"):
        rt.run(3, arrivals=[0.0])
    with pytest.raises(ValueError):
        ArrivalSource([-1.0])


def test_runtime_rejects_workload_plane_events():
    """arrive/mix TimelineEvents are consumed by the serving layer; feeding
    them to the execution plane is a usage error with an actionable hint."""
    _, rt = mk_runtime([1.0])
    ev = TimelineEvent(0.0, "arrive", (0.0, 1.0))
    with pytest.raises(ValueError, match="workload-plane"):
        rt.run(2, grain_cost=1.0, timeline=(ev,), timeline_relative=True)


# ========================================================= wave-quota fix
def test_wave_plan_caps_per_replica_initial_queue():
    """The old wave quota was global (depth x live count): a fast replica
    could be handed nearly the whole wave and start it deeper than
    max_queue_depth.  The plan cap enforces the depth per replica."""
    server = mk_server([("fast", 8.0, 2), ("slow", 1.0, 2)],
                       max_queue_depth=4)
    now = server.runtime.clock
    server.tracker.rejoin("fast", 8.0, now)
    server.tracker.rejoin("slow", 1.0, now)
    uncapped = server.runtime.plan(8)
    by_name = dict(zip(uncapped.workers, uncapped.shares))
    assert by_name["fast"] > 4, "precondition: the homogenized share must breach the cap"
    capped = server._wave_plan(8)
    assert capped is not None
    shares = dict(zip(capped.workers, capped.shares))
    assert all(s <= 4 for s in shares.values())
    assert sum(shares.values()) == 8
    assert shares["slow"] == 4  # the excess lands on the replica with room


def test_wave_plan_no_cap_is_bitwise_identical_path():
    """Equal perfs never breach the cap: _wave_plan must return None so the
    closed-loop wave path (and its plans) stay exactly as before."""
    server = mk_server([("a", 2.0, 2), ("b", 2.0, 2)], max_queue_depth=4)
    assert server._wave_plan(8) is None


def test_wave_serve_respects_per_replica_depth():
    """End-to-end: with a 8x perf spread, every wave's *initial* admission
    must still respect max_queue_depth per replica (the capped plan), and
    all requests decode exactly once."""
    server = mk_server([("fast", 8.0, 2), ("slow", 1.0, 2)],
                       max_queue_depth=3)
    now = server.runtime.clock
    server.tracker.rejoin("fast", 8.0, now)
    server.tracker.rejoin("slow", 1.0, now)
    reqs = mk_requests(6, max_new=4)
    rep = server.serve(reqs)
    assert rep.n_requests == 6
    for r in reqs:
        assert r.out_tokens == expected_tokens(r)


# ============================================================ serve_stream
def test_stream_traces_and_latency_stats():
    server = mk_server([("r0", 4.0, 2), ("r1", 2.0, 2)], max_queue_depth=8)
    reqs = mk_requests(10, max_new=4)
    arrive = [0.5 * i for i in range(10)]
    rep = server.serve_stream(reqs, arrive)
    assert rep.n_served == 10 and rep.n_shed == 0
    assert len(rep.traces) == 10
    for t, a in zip(rep.traces, arrive):
        assert t.arrive_s == a
        assert t.first_token_s is not None and t.first_token_s >= a
        assert t.finish_s >= t.first_token_s
        assert t.ttft_s >= 0 and t.latency_s > 0
    lat = rep.latency
    assert math.isfinite(lat.p50_ttft_s) and math.isfinite(lat.p99_ttft_s)
    assert lat.p50_ttft_s <= lat.p99_ttft_s
    # Exactly-once decode under streaming admission.
    for r in reqs:
        assert r.out_tokens == expected_tokens(r)


def test_stream_shed_traces_on_overflow():
    server = mk_server([("r0", 1.0, 1)], max_queue_depth=1)
    reqs = mk_requests(8, max_new=6)
    rep = server.serve_stream(reqs, [0.0] * 8, overflow="shed")
    assert rep.n_shed > 0
    assert rep.n_served + rep.n_shed == 8
    assert rep.shed_rate == pytest.approx(rep.n_shed / 8)
    for t in rep.traces:
        if t.shed:
            assert t.first_token_s is None and t.finish_s is None
            assert t.worker is None and t.tokens == 0
    assert rep.latency.n_shed == rep.n_shed


def test_stream_goodput_under_deadline():
    server = mk_server([("r0", 4.0, 2)], max_queue_depth=8)
    reqs = mk_requests(6, max_new=4)
    rep = server.serve_stream(reqs, [i * 0.5 for i in range(6)],
                              deadline_s=1e9)
    assert rep.latency.n_within_deadline == 6
    assert rep.latency.goodput_rps > 0


def test_stream_autoscale_joins_and_serves():
    """The reactive loop end-to-end: a breached p99-TTFT rule joins a
    replica mid-stream (engine lazily built) and that replica takes work."""
    server = mk_server([("r0", 2.0, 2)], max_queue_depth=2,
                       engine_factory=stub_factory)
    reqs = mk_requests(30, max_new=6)
    rule = ScaleRule(add=1, metric="p99", threshold=0.01, window=4)
    rep = server.serve_stream(reqs, [0.2 * i for i in range(30)],
                              scale_rules=[rule])
    assert rep.joined == ("scale0",)
    assert rep.shares.get("scale0", 0) > 0
    assert "scale0" in rep.worker_busy
    for r in reqs:
        assert r.out_tokens == expected_tokens(r)


def test_stream_scale_rule_not_breached_never_joins():
    server = mk_server([("r0", 8.0, 4)], max_queue_depth=8,
                       engine_factory=stub_factory)
    reqs = mk_requests(6, max_new=3)
    rule = ScaleRule(add=1, metric="p99", threshold=1e9, window=2)
    rep = server.serve_stream(reqs, [2.0 * i for i in range(6)],
                              scale_rules=[rule])
    assert rep.joined == ()


def test_scale_rules_require_engine_factory():
    server = mk_server([("r0", 2.0, 2)], max_queue_depth=4)
    rule = ScaleRule(add=1, metric="p99", threshold=0.1)
    with pytest.raises(ValueError, match="engine_factory"):
        server.serve_stream(mk_requests(4), [0.0] * 4, scale_rules=[rule])


def test_stream_survives_mid_stream_halve():
    """The acceptance shape: a mid-stream perf halving migrates load and the
    survivors still homogenize (quality <= 1.3)."""
    server = mk_server([("r0", 4.0, 2), ("r1", 4.0, 2)], max_queue_depth=8)
    now = server.runtime.clock
    for n in ("r0", "r1"):
        server.tracker.rejoin(n, 8.0, now)  # rate units: perf x slots
    reqs = mk_requests(24, max_new=6)
    halve = TimelineEvent(2.0, "perf", "r0", perf=2.0)
    rep = server.serve_stream(reqs, [0.1 * i for i in range(24)],
                              timeline=(halve,))
    assert rep.n_shed == 0
    assert rep.quality <= 1.3
    for r in reqs:
        assert r.out_tokens == expected_tokens(r)


# ============================================== scenario workload grammar
FLEET = FleetSpec.parse("w0=4x2,w1=2x2")


def test_workload_clause_round_trip():
    s = "arrive:poisson(8)@0-30;burst:64@10;mix:len*1.5@12;scale:+2@p99>0.5"
    sc = Scenario.parse(s)
    assert str(Scenario.parse(str(sc))) == str(sc)
    assert sc.has_workload
    assert sc.scale_rules == (ScaleRule(add=2, metric="p99", threshold=0.5),)


def test_workload_clauses_split_on_whitespace():
    sc = Scenario.parse("arrive:poisson(8)@0-30 burst:64@10 scale:+2@p99>0.5")
    assert len(sc.clauses) == 2 and len(sc.scale_rules) == 1


def test_workload_grammar_rejections():
    for bad in (
        "arrive:uniform(8)@0-30",     # only poisson processes
        "burst:0@10",                 # empty burst
        "mix:len*0@12",               # non-positive factor
        "scale:+0@p99>0.5",           # must add at least one replica
        "scale:+1@p200>0.5",          # not a percentile
        "scale:+1@p99>0",             # non-positive threshold
    ):
        with pytest.raises(ValueError):
            Scenario.parse(bad)


def test_arrivals_bitwise_deterministic_by_seed():
    sc = Scenario.parse("arrive:poisson(8)@0-10;burst:4@2")
    a = sc.compile(FLEET, phase_s=10.0, seed=5)
    b = sc.compile(FLEET, phase_s=10.0, seed=5)
    assert a == b, "same seed must materialize bitwise-identical arrivals"
    c = sc.compile(FLEET, phase_s=10.0, seed=6)
    assert a != c


def test_phase_relative_arrive_anchors_to_true_phase_start():
    """arrive:poisson(L)@1:50% with no '-T2' spans one phase estimate from
    the *true* window-1 start — the satellite's phase-relative case."""
    sc = Scenario.parse("arrive:poisson(4)@1:50%")
    assert sc.needs_estimates
    sched = sc.schedule(FLEET, phase_s=10.0, seed=3)
    assert sched.phase_events(0, 0.0) == ()
    evs = sched.phase_events(1, 12.0)
    assert len(evs) == 1 and evs[0].kind == "arrive"
    assert evs[0].time_s == pytest.approx(12.0 + 5.0)
    assert all(off >= 0 for off in evs[0].worker)
    assert sched.exhausted


def test_materialize_workload_splits_planes():
    sc = Scenario.parse("arrive:poisson(6)@0-5;mix:len*2@3;halve:w0@1")
    plan = materialize_workload(sc.schedule(FLEET, phase_s=5.0, seed=1), 5.0)
    assert plan.n_requests == len(plan.arrive_s) > 0
    assert list(plan.arrive_s) == sorted(plan.arrive_s)
    assert plan.mix == ((3.0, 2.0),)
    assert plan.lengths_factor(2.9) == 1.0
    assert plan.lengths_factor(3.0) == 2.0
    assert [e.kind for e in plan.timeline] == ["perf"]


# ================================================================= cluster
def test_cluster_serve_open_loop_end_to_end():
    cl = Cluster("w0=4x2,w1=2x2", priors="spec", seed=3)
    pool = mk_requests(200, max_new=6)
    rep = cl.serve(
        ServeJob(pool, engine_factory=stub_factory, max_queue_depth=4,
                 overflow="shed", deadline_s=5.0),
        scenario="arrive:poisson(8)@0-10 burst:16@2 halve:w0@1:0% "
                 "scale:+1@p99>0.2/10",
    )
    assert rep.kind == "serve"
    assert rep.metrics["mode"] == "open-loop"
    assert rep.n_phases == 1 and rep.phases[0].label == "stream"
    lat = rep.latency
    assert lat is not None
    assert math.isfinite(lat.p50_ttft_s) and math.isfinite(lat.p99_ttft_s)
    assert rep.metrics["n_requests"] < len(pool)  # arrivals sized the stream
    # The autoscaled replica joined AND shows up in the unified timelines.
    assert rep.metrics["joined"] == ["scale0"]
    assert rep.worker_timelines["scale0"].n_grains > 0
    assert "latency[" in rep.summary()


def test_cluster_serve_wave_mode_unchanged_without_workload():
    cl = Cluster("w0=4x2,w1=2x2")
    rep = cl.serve(ServeJob(mk_requests(8, max_new=4),
                            engine_factory=stub_factory))
    assert rep.latency is None
    assert all(p.label == "wave" for p in rep.phases)
    assert "mode" not in rep.metrics


def test_cluster_serve_mix_scales_late_arrivals():
    cl = Cluster("w0=4x2", priors="spec")
    pool = mk_requests(100, max_new=4)
    rep = cl.serve(
        ServeJob(pool, engine_factory=stub_factory, window_s=4.0),
        scenario="arrive:poisson(4)@0-8 mix:len*2@4",
    )
    served = rep.artifact
    assert any(r.max_new_tokens == 8 for r in served), \
        "requests arriving after the mix shift must carry the scaled budget"
    assert any(r.max_new_tokens == 4 for r in served)


def test_cluster_serve_pool_smaller_than_arrivals_is_actionable():
    cl = Cluster("w0=4x2", priors="spec")
    with pytest.raises(ValueError, match="request pool"):
        cl.serve(
            ServeJob(mk_requests(3, max_new=4), engine_factory=stub_factory),
            scenario="arrive:poisson(50)@0-10",
        )


def test_simulate_and_train_reject_workload_scenarios():
    cl = Cluster("w0=2,w1=1")
    with pytest.raises(ValueError, match="Cluster.serve"):
        cl.simulate(100, scenario="arrive:poisson(2)@0-5")
    with pytest.raises(ValueError, match="Cluster.serve"):
        cl.simulate(100, scenario="scale:+1@p99>0.5")
    with pytest.raises(ValueError, match="Cluster.serve"):
        cl.train(TrainJob(model=None, steps=1),
                 scenario="burst:8@1")
