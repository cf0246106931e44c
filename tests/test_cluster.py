"""The declarative Cluster API: spec grammar, scenario DSL, unified reports.

Tier-1 (timing-scale only — stub engines, numpy matmuls, no model compile):

  - FleetSpec grammar: legacy forms parse, canonical round-trip, actionable
    rejection of malformed items,
  - Scenario DSL: parse -> canonical -> parse round-trip, compile ->
    TimelineEvent equivalence with the hand-built timelines it replaces, and
    run-level equivalence (a DSL-scripted job == the raw-runtime job),
  - backend profiles: slopes are calibrated via overhead_slope_fit and flow
    into per-provider overhead,
  - the Cluster facade: all three surfaces return RunReports; a DSL-scripted
    mid-run perf-halving holds adaptive quality <= 1.3 in serve (train is
    asserted at model scale in the slow tier, test_train_loop.py),
  - the ROADMAP join fix: a replica joined mid-wave via Scenario lazily
    constructs its engine before admission.
"""

import numpy as np
import pytest
from stub_engine import StubEngine, expected_tokens, mk_requests

from repro.cluster import (
    PROFILES,
    BackendProfile,
    Cluster,
    FleetSpec,
    MatmulJob,
    Scenario,
    ServeJob,
    SimJob,
    WorkerSpec,
    get_profile,
)
from repro.core import (
    AsyncRuntime,
    ClusterSim,
    PerformanceTracker,
    ServiceProvider,
    SimWorker,
    TDAServer,
    ThinClient,
    TimelineEvent,
    overhead_slope_fit,
)


def stub_factory(spec: WorkerSpec) -> StubEngine:
    return StubEngine(max_batch=spec.concurrency, name=spec.name)


# ===================================================================== spec
def test_fleet_spec_legacy_replicas_grammar():
    f = FleetSpec.parse("8x4:4x2:2x1", prefix="r")
    assert f.names == ("r0", "r1", "r2")
    assert f.perfs == (8.0, 4.0, 2.0)
    assert [w.concurrency for w in f.workers] == [4, 2, 1]


def test_fleet_spec_legacy_pods_grammar():
    f = FleetSpec.parse("4:3:2:1", prefix="pod")
    assert f.names == ("pod0", "pod1", "pod2", "pod3")
    assert f.perfs == (4.0, 3.0, 2.0, 1.0)
    assert all(w.concurrency == 1 for w in f.workers)


def test_fleet_spec_named_profiles_and_multiplier():
    f = FleetSpec.parse("fast=8x4@dcn,edge=1x2,2.0x4*3")
    assert f.names == ("fast", "edge", "w2", "w3", "w4")
    assert f.worker("fast").profile == "dcn"
    assert f.worker("w3").perf == 2.0 and f.worker("w3").concurrency == 4


def test_fleet_spec_canonical_round_trip():
    for s in ("8x4:4x2:2x1", "4:3:2:1", "fast=8x4@dcn,edge=1x2", "2.0x8,1.0x4"):
        f = FleetSpec.parse(s)
        assert FleetSpec.parse(str(f)) == f, s


def test_fleet_spec_from_dicts_and_perfs():
    f = FleetSpec.from_dicts([
        {"name": "a", "perf": 2.0, "concurrency": 8, "profile": "lan-1g"},
        {"perf": 1.0},
        (3.0, 2),
    ])
    assert f.names == ("a", "w1", "w2")
    assert f.worker("w2").concurrency == 2
    g = FleetSpec.from_perfs([1.0, 0.5], prefix="sp")
    assert g.names == ("sp0", "sp1")
    assert FleetSpec.parse(f.workers) == f          # sequence of WorkerSpecs


def test_fleet_spec_take_and_rates():
    f = FleetSpec.parse("8x4:4x2:2x1")
    assert f.take(2).names == ("w0", "w1")
    assert f.total_rate() == 8 * 4 + 4 * 2 + 2 * 1
    assert f.total_perf() == 14.0


@pytest.mark.parametrize("bad,match", [
    ("", "empty fleet spec"),
    ("abc", "bad worker spec"),
    ("2x", "bad worker spec"),
    ("x4", "bad worker spec"),
    ("a=2,a=3", "duplicate worker name"),
    ("2@nope", "unknown backend profile"),
    ("name=2*3", "anonymous"),
    ("0x4", "perf must be > 0"),
])
def test_fleet_spec_malformed_rejected_actionably(bad, match):
    with pytest.raises((ValueError, KeyError), match=match):
        FleetSpec.parse(bad)


def test_fleet_spec_zero_concurrency_rejected():
    with pytest.raises(ValueError, match="concurrency must be >= 1"):
        WorkerSpec("a", 1.0, concurrency=0)


def test_fleet_spec_unknown_worker_lookup_names_fleet():
    f = FleetSpec.parse("4:2")
    with pytest.raises(KeyError, match="known workers"):
        f.worker("nope")


# ================================================================= scenario
def test_scenario_round_trip_canonical():
    text = ("halve:w0@25%;degrade:w1*0.2@3:30%;perf:w2=1.5@12;kill:w3@9;"
            "join:w4=1.5x4@12;ramp:w0*0.25@2..8/4;jitter:0.05")
    sc = Scenario.parse(text)
    assert str(sc) == text
    assert str(Scenario.parse(str(sc))) == text
    assert sc.jitter == 0.05
    assert Scenario.parse(None) == Scenario.none()
    assert not Scenario.none()


def test_scenario_from_arg_legacy_names():
    assert str(Scenario.from_arg("halving", "r0")) == "halve:r0@25%"
    assert str(Scenario.from_arg("kill", "r0")) == "kill:r0@25%"
    assert not Scenario.from_arg("none", "r0")
    assert str(Scenario.from_arg("degrade:x*0.5@1", "r0")) == "degrade:x*0.5@1"


@pytest.mark.parametrize("bad,match", [
    ("explode:w0@5", "bad scenario clause"),
    ("halve:w0", "missing '@TIME'"),
    ("halve:w0@soon", "bad scenario time"),
    ("degrade:w0@5", "want degrade:W\\*FACTOR@TIME"),
    ("degrade:w0*0@5", "factor must be > 0"),
    ("perf:w0=0@5", "perf must be > 0"),
    ("halve:w0@150%", "must be <= 100%"),
    ("ramp:w0*0.5@2..8", "bad ramp clause"),
    ("jitter:lots", "want jitter:SIGMA"),
])
def test_scenario_malformed_rejected_actionably(bad, match):
    with pytest.raises(ValueError, match=match):
        Scenario.parse(bad)


def test_scenario_compile_equivalent_to_hand_built_timeline():
    """The DSL replaces the hand-rolled builders: compiling
    'halve:r0@25%' must produce exactly the TimelineEvent the serve
    launcher's scenario_timeline() used to build by hand."""
    fleet = FleetSpec.parse("8x4:4x2:2x1", prefix="r")
    phase_s = 432 / 42.0                     # cost / fleet rate, as before
    tl = Scenario.parse("halve:r0@25%").compile(fleet, phase_s=phase_s)
    assert tl == (TimelineEvent(0.25 * phase_s, "perf", "r0", perf=4.0),)
    tl = Scenario.parse("kill:r0@25%").compile(fleet, phase_s=phase_s)
    assert tl == (TimelineEvent(0.25 * phase_s, "kill", "r0"),)


def test_scenario_compile_relative_perf_is_cumulative():
    fleet = FleetSpec.parse("4:2", prefix="w")
    tl = Scenario.parse("halve:w0@1;halve:w0@2;degrade:w1*0.25@3").compile(fleet)
    assert [ev.perf for ev in tl] == [2.0, 1.0, 0.5]


def test_scenario_compile_phase_qualified_times():
    fleet = FleetSpec.parse("4:2")
    tl = Scenario.parse("halve:w0@2:50%").compile(fleet, phase_s=10.0,
                                                  stride_s=14.0)
    assert tl[0].time_s == pytest.approx(2 * 14.0 + 5.0)


def test_scenario_compile_ramp_stages():
    fleet = FleetSpec.parse("4:2")
    tl = Scenario.parse("ramp:w0*0.25@2..8/4").compile(fleet)
    assert [ev.time_s for ev in tl] == [2.0, 4.0, 6.0, 8.0]
    perfs = [ev.perf for ev in tl]
    assert perfs[-1] == pytest.approx(1.0)          # 4.0 * 0.25
    assert all(a > b for a, b in zip(perfs, perfs[1:]))  # monotone stages


def test_scenario_compile_join_uses_fleet_spec_or_explicit():
    fleet = FleetSpec.parse("a=4x2,b=2x1")
    tl = Scenario.parse("kill:a@1;join:a@5;join:c=1.5x4@9").compile(fleet)
    assert tl[0].kind == "kill"
    rejoin, newjoin = tl[1], tl[2]
    assert rejoin.kind == "join" and rejoin.worker.perf == 4.0
    assert newjoin.worker.name == "c" and newjoin.perf == 1.5


def test_scenario_compile_unknown_worker_actionable():
    fleet = FleetSpec.parse("4:2")
    with pytest.raises(ValueError, match="unknown worker 'nope'.*fleet workers"):
        Scenario.parse("halve:nope@5").compile(fleet)
    with pytest.raises(ValueError, match="needs an explicit spec"):
        Scenario.parse("join:nope@5").compile(fleet)


def test_scenario_relative_time_requires_estimate():
    fleet = FleetSpec.parse("4:2")
    sc = Scenario.parse("halve:w0@25%")
    assert sc.needs_estimates
    with pytest.raises(ValueError, match="phase-relative"):
        sc.compile(fleet)


# ===================================== DSL-built run == hand-built run
def test_dsl_run_equivalent_to_hand_built_runtime_run():
    """A Cluster.simulate run scripted via the DSL must reproduce the raw
    AsyncRuntime run it replaces: same makespans, qualities and shares."""
    # size chosen so scope lengths divide exactly: the facade's plan-based
    # phase estimate and the old work/sum(perfs) arithmetic coincide.
    size, n_jobs = 250, 3
    unit = ClusterSim.unit_cost(size)
    perfs = (4.0, 4.0, 2.0)
    est = size * unit / sum(perfs)

    # Hand-built (the pre-DSL benchmark pattern): oracle tracker, raw event.
    workers = [SimWorker(f"w{i}", p) for i, p in enumerate(perfs)]
    tracker = PerformanceTracker(alpha=0.5, dead_after_s=1e18)
    for w in workers:
        tracker.rejoin(w.name, w.perf, 0.0)
    rt = AsyncRuntime(workers, tracker=tracker)
    hand = []
    for k in range(n_jobs):
        timeline = (TimelineEvent(0.25 * est, "perf", "w0", perf=2.0),) if k == 0 else ()
        res = rt.run(size, grain_cost=unit, timeline=timeline,
                     timeline_relative=True)
        hand.append(res)

    # DSL-built through the facade (overhead strides the clock identically
    # in both runs only if we compare compute time, which is what we do).
    cluster = Cluster(FleetSpec.from_perfs(perfs), priors="spec")
    rep = cluster.simulate(SimJob(size=size, n_jobs=n_jobs),
                           scenario="halve:w0@25%")
    assert rep.n_phases == n_jobs
    for res, p in zip(hand, rep.phases, strict=True):
        assert p.metrics["compute_s"] == pytest.approx(res.makespan)
        assert p.quality == pytest.approx(res.homogenization_quality())
        assert dict(p.shares) == res.shares()


def test_simulate_adaptive_holds_line_static_does_not():
    """The sim-side acceptance: DSL-scripted mid-job halving, adaptive
    quality stays low while the static plan drags at the straggler."""
    fleet = FleetSpec.parse("4:4")
    sc = "halve:w0@25%"
    ada = Cluster(fleet, priors="spec").simulate(
        SimJob(size=400), scenario=sc)
    sta = Cluster(fleet, priors="spec", adaptive=False).simulate(
        SimJob(size=400), scenario=sc)
    assert ada.homogenization_quality() <= 1.3, ada.summary()
    assert sta.homogenization_quality() >= 1.6, sta.summary()
    assert ada.phases[0].metrics["compute_s"] < sta.phases[0].metrics["compute_s"]
    assert ada.n_migrated > 0


def test_simulate_scenario_join_adds_worker():
    fleet = FleetSpec.parse("2:2")
    rep = Cluster(fleet, priors="spec").simulate(
        SimJob(size=300), scenario="join:w9=4@10%")
    assert rep.shares().get("w9", 0) > 0
    assert "w9" in rep.worker_timelines


def test_simulate_report_fields_consistent():
    # size 800: unit work, where the paper's model says distribution pays
    # (smaller sizes are legitimately overhead-dominated, speedup < 1).
    rep = Cluster("4:2", priors="spec").simulate(SimJob(size=800, n_jobs=2))
    assert rep.kind == "simulate"
    assert rep.fleet == "w0=4,w1=2"
    assert rep.scenario == ""
    assert rep.work_done == 1600
    assert sum(rep.shares().values()) == 1600
    assert rep.sim_time_s == pytest.approx(sum(rep.phase_times()))
    assert rep.predicted_speedup > 1.0
    assert rep.measured_speedup > 1.0
    tl = rep.worker_timelines
    assert set(tl) == {"w0", "w1"}
    assert tl["w0"].n_grains + tl["w1"].n_grains == 1600
    assert "quality" in rep.summary() or "quality=" in rep.summary()


# ================================================================= profiles
def test_profiles_are_calibrated_via_slope_fit():
    p = get_profile("paper-ethernet")
    loads = [c[0] for c in p.calibration]
    ovh = [c[1] for c in p.calibration]
    assert p.overhead_slope == pytest.approx(overhead_slope_fit(loads, ovh))
    assert p.overhead_slope == pytest.approx(20.0, rel=0.05)
    assert get_profile("dcn").overhead_slope > 100 * p.overhead_slope
    assert get_profile(None).name == "paper-ethernet"
    assert get_profile(p) is p
    with pytest.raises(KeyError, match="known:"):
        get_profile("wat")
    with pytest.raises(ValueError, match="calibration"):
        BackendProfile("thin", ((1.0, 0.1),))


def test_fleet_overhead_model_combines_profiles():
    same = FleetSpec.parse("2@lan-1g,2@lan-1g")
    m_lan = PROFILES["lan-1g"].overhead_slope
    assert same.overhead_model().m == pytest.approx(m_lan)
    mixed = FleetSpec.parse("2@lan-1g,2@paper-ethernet")
    m_eth = PROFILES["paper-ethernet"].overhead_slope
    assert (min(m_eth, m_lan) < mixed.overhead_model().m < max(m_eth, m_lan))


def test_service_provider_profile_changes_distribution_overhead():
    """Per-backend slopes: the same matmul pays less distribution overhead
    on fast links — O = sum rows_i / m_i instead of the single fleet slope."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((32, 8)).astype(np.float32)
    b = rng.standard_normal((8, 8)).astype(np.float32)

    def run(profile):
        providers = [ServiceProvider(f"sp{i}", 2.0, profile=profile)
                     for i in range(2)]
        client = ThinClient(TDAServer(providers))
        out, t = client.matmul(a, b)
        np.testing.assert_array_equal(out, a @ b)
        return t - client.last_result.makespan

    ovh_default = run(None)                     # falls back to sim slope
    ovh_eth = run("paper-ethernet")
    ovh_dcn = run("dcn")
    assert ovh_dcn < ovh_eth / 10
    assert ovh_eth == pytest.approx(
        32 / PROFILES["paper-ethernet"].overhead_slope, rel=1e-6)
    assert ovh_default == pytest.approx(32 / 20.0)  # the old hardcoded path


def test_matmul_job_through_facade_exact_and_profiled():
    rng = np.random.default_rng(1)
    a = rng.standard_normal((24, 8)).astype(np.float32)
    b = rng.standard_normal((8, 8)).astype(np.float32)
    rep = Cluster("2@dcn,2@dcn,1@dcn").simulate(MatmulJob(a, b, n_jobs=2))
    assert rep.metrics["max_abs_err"] == 0.0
    np.testing.assert_array_equal(rep.artifact, a @ b)
    assert sum(rep.shares().values()) == 2 * 12       # 2-row grains x 2 jobs
    # dcn links: distribution overhead is far below the paper-ethernet cost
    assert rep.phases[0].metrics["overhead_s"] < 24 / 20.0 / 10


def test_matmul_mixed_profiles_charge_default_not_blended():
    """Regression: in a mixed-profile fleet, an unprofiled worker is charged
    the *default* profile's slope, not the blended fleet slope (which would
    double-count the mix)."""
    rng = np.random.default_rng(2)
    a = rng.standard_normal((24, 8)).astype(np.float32)
    b = rng.standard_normal((8, 8)).astype(np.float32)
    rep = Cluster("fast=1@local,plain=1").simulate(MatmulJob(a, b))
    shares = rep.shares()
    m_local = PROFILES["local"].overhead_slope
    m_eth = PROFILES["paper-ethernet"].overhead_slope
    expected = (2 * shares.get("fast", 0)) / m_local + \
               (2 * shares.get("plain", 0)) / m_eth
    assert rep.phases[0].metrics["overhead_s"] == pytest.approx(expected)


# ==================================================================== serve
def test_serve_facade_dsl_halving_quality_within_1_3():
    """The serving acceptance, end-to-end through the facade: warm wave,
    then a DSL-scripted mid-bundle perf halving; adaptive homogenization
    quality must hold <= 1.3 and every decode must stay exactly-once."""
    cluster = Cluster("a=4x2,b=4x2")
    cluster.serve(ServeJob(mk_requests(64), engine_factory=stub_factory,
                           max_queue_depth=64))
    reqs = mk_requests(64)
    rep = cluster.serve(ServeJob(reqs, engine_factory=stub_factory,
                                 max_queue_depth=64),
                        scenario="halve:a@20%")
    assert rep.kind == "serve"
    assert rep.homogenization_quality() <= 1.3, rep.summary()
    assert rep.n_migrated > 0
    for r in reqs:
        assert r.done and r.out_tokens == expected_tokens(r), r.rid


def test_serve_facade_scenario_join_lazily_builds_engine():
    """The ROADMAP join bug, fixed: a replica joining mid-wave without an
    engine must construct one (from its WorkerSpec) before admission and
    actually serve requests."""
    cluster = Cluster("a=2x2,b=2x2")
    reqs = mk_requests(48, prompt_len=2, max_new=8)
    rep = cluster.serve(ServeJob(reqs, engine_factory=stub_factory,
                                 max_queue_depth=64),
                        scenario="join:c=4x4@10%")
    assert rep.shares().get("c", 0) > 0, rep.shares()
    for r in reqs:
        assert r.done and r.out_tokens == expected_tokens(r), r.rid
    # the lazily-built engine persists on the server for later waves
    server = cluster._server
    assert "c" in server.engines
    assert server.engines["c"].max_batch == 4
    rep2 = cluster.serve(ServeJob(mk_requests(24), engine_factory=stub_factory,
                                  max_queue_depth=64))
    assert rep2.shares().get("c", 0) > 0


def test_serve_facade_kill_then_rejoin_via_scenario():
    cluster = Cluster("a=2x2,b=2x2")
    reqs = mk_requests(40, max_new=8)
    rep = cluster.serve(
        ServeJob(reqs, engine_factory=stub_factory, max_queue_depth=64),
        scenario="kill:a@10%;join:a@70%",
    )
    for r in reqs:
        assert r.done and r.out_tokens == expected_tokens(r), r.rid
    assert rep.homogenization_quality() >= 1.0
    # after the rejoin, 'a' is live again for the next workload
    rep2 = cluster.serve(ServeJob(mk_requests(16), engine_factory=stub_factory,
                                  max_queue_depth=64))
    assert rep2.shares().get("a", 0) > 0


@pytest.mark.parametrize("settings, rehomogenize, threshold", [
    ({}, True, 0.05),
    ({"adaptive": False}, False, 0.05),
    ({"replan_threshold": 0.2}, True, 0.2),
], ids=["default", "static", "threshold"])
def test_serve_settings_reach_the_runtime_through_fleet_server(
        monkeypatch, settings, rehomogenize, threshold):
    """Cluster's ``adaptive`` and ``replan_threshold`` are FleetServer
    constructor arguments, and the serving runtime is built with them."""
    from repro.serve import fleet

    seen = {}
    init = fleet.FleetServer.__init__

    def spy(self, *args, **kwargs):
        seen.update(kwargs)
        init(self, *args, **kwargs)

    monkeypatch.setattr(fleet.FleetServer, "__init__", spy)
    cluster = Cluster("a=4x2,b=2x2", **settings)
    cluster.serve(ServeJob(mk_requests(8), engine_factory=stub_factory))
    assert seen["adaptive"] is settings.get("adaptive", True)
    assert seen["replan_threshold"] == threshold
    rt = cluster._server.runtime
    assert rt.rehomogenize is rehomogenize and rt.steal is rehomogenize
    assert rt.replan_threshold == threshold


def test_serve_facade_rejects_jitter_and_missing_engines():
    cluster = Cluster("a=2x2")
    with pytest.raises(ValueError, match="jitter"):
        cluster.serve(ServeJob(mk_requests(2), engine_factory=stub_factory),
                      scenario="jitter:0.1")
    with pytest.raises(ValueError, match="engine_factory"):
        Cluster("a=2x2").serve(ServeJob(mk_requests(2)))


def test_serve_report_worker_timelines_cover_fleet():
    cluster = Cluster("a=4x2,b=2x1")
    rep = cluster.serve(ServeJob(mk_requests(20), engine_factory=stub_factory,
                                 max_queue_depth=32))
    tl = rep.worker_timelines
    assert set(tl) <= {"a", "b"} and tl
    assert sum(w.n_grains for w in tl.values()) == 20
    assert all(w.busy_s > 0 for w in tl.values())


def test_launch_serve_shims_preserve_legacy_contract():
    """The deprecated launcher shims stay behavior-compatible: bare-perf
    replicas default to 4 slots (the old parse_replicas contract), and
    scenario_timeline builds the exact event the hand-rolled version did."""
    from repro.launch.serve import parse_replicas, scenario_timeline

    assert parse_replicas("8x4:4x2:2x1") == [(8.0, 4), (4.0, 2), (2.0, 1)]
    assert parse_replicas("8:4:2") == [(8.0, 4), (4.0, 4), (2.0, 4)]
    reqs = mk_requests(4, prompt_len=2, max_new=6)        # cost 4 * 8 = 32
    specs = [(8.0, 4), (4.0, 2)]
    rate = 8 * 4 + 4 * 2
    assert scenario_timeline("halving", specs, reqs) == (
        TimelineEvent(0.25 * 32 / rate, "perf", "r0", perf=4.0),)
    assert scenario_timeline("kill", specs, reqs) == (
        TimelineEvent(0.25 * 32 / rate, "kill", "r0"),)
    assert scenario_timeline("none", specs, reqs) == ()


def test_simulate_measured_speedup_tracks_predicted_without_faults():
    """Regression: predicted_speedup must charge the overhead model with the
    same *load units* the run itself pays, at any job size — with oracle
    priors and no fault, measured and predicted agree closely."""
    for size in (200, 400, 800):
        rep = Cluster("4:2:1", priors="spec").simulate(SimJob(size=size))
        assert rep.measured_speedup == pytest.approx(
            rep.predicted_speedup, rel=0.05), (size, rep.summary())


def test_report_finish_times_are_run_relative_across_phases():
    """Regression: multi-phase worker finish times accumulate preceding
    phase spans instead of resetting each phase."""
    rep = Cluster("4:2", priors="spec").simulate(SimJob(size=120, n_jobs=3))
    first_two = sum(p.sim_time_s for p in rep.phases[:2])
    last_finish = max(w.finish_s for w in rep.worker_timelines.values())
    assert last_finish > first_two
    assert last_finish <= rep.sim_time_s + 1e-9


def test_serve_rejects_mismatched_job_against_cached_fleet():
    """Regression: the persistent fleet server must not silently decode a
    new job with engines built for a different factory/model."""
    cluster = Cluster("a=2x2")
    cluster.serve(ServeJob(mk_requests(4), engine_factory=stub_factory))
    with pytest.raises(ValueError, match="fresh=True"):
        cluster.serve(ServeJob(
            mk_requests(4),
            engine_factory=lambda spec: StubEngine(max_batch=2, name=spec.name),
        ))
    # same factory is fine; fresh=True rebuilds for a new one
    cluster.serve(ServeJob(mk_requests(4), engine_factory=stub_factory))
    rep = cluster.serve(ServeJob(
        mk_requests(4),
        engine_factory=lambda spec: StubEngine(max_batch=2, name=spec.name),
        fresh=True))
    assert rep.work_done == 4 * 6


# ============================================================ cluster misc
def test_cluster_rejects_bad_priors_and_scenario_types():
    with pytest.raises(ValueError, match="priors"):
        Cluster("4:2", priors="oracle")
    with pytest.raises(TypeError, match="Scenario"):
        Cluster("4:2").simulate(SimJob(size=10), scenario=42)


def test_cluster_same_spec_and_scenario_drive_sim_and_serve():
    """The unification claim: one FleetSpec + one Scenario object drive two
    different workloads without translation."""
    fleet = FleetSpec.parse("a=4x2,b=4x2")
    sc = Scenario.parse("halve:a@25%")
    sim = Cluster(fleet, priors="spec").simulate(SimJob(size=200), scenario=sc)
    srv = Cluster(fleet).serve(
        ServeJob(mk_requests(32), engine_factory=stub_factory,
                 max_queue_depth=64), scenario=sc)
    assert sim.fleet == srv.fleet == str(fleet)
    assert sim.scenario == srv.scenario == "halve:a@25%"
    assert {p.label for p in sim.phases} == {"job"}
    assert {p.label for p in srv.phases} == {"wave"}


# ========================================================== roles (disagg)
ROLED = "pf0=2.0^prefill,dc0=1.0x4^decode,dc1=1.0x4^decode"


def test_roled_fleet_rejected_outside_serve():
    with pytest.raises(ValueError, match="only Cluster.serve"):
        Cluster(ROLED).simulate(SimJob(size=10))
    with pytest.raises(ValueError, match="only Cluster.serve"):
        Cluster(ROLED).train(None)


def test_roled_fleet_pool_composition_validated():
    with pytest.raises(ValueError, match="mixes roled and mixed"):
        Cluster("a=1^prefill,b=1").serve(
            ServeJob(mk_requests(2), engine_factory=stub_factory))
    with pytest.raises(ValueError, match="at least one"):
        Cluster("a=1^prefill,b=1^prefill").serve(
            ServeJob(mk_requests(2), engine_factory=stub_factory))


def test_roled_fleet_scenario_interactions_rejected():
    def serve(fleet, sc=None, n=2):
        return Cluster(fleet).serve(
            ServeJob(mk_requests(n), engine_factory=stub_factory),
            scenario=sc)

    # a joined replica has no role -> joins are ambiguous on a roled fleet
    with pytest.raises(ValueError, match="joined replica"):
        serve(ROLED, "join:new=1x2@1")
    # scale: rules join replicas too, just reactively
    with pytest.raises(ValueError, match="scale: rules cannot target"):
        serve(ROLED, "arrive:poisson(4)@0-5;scale:+1@p99>0.1", n=30)
    # killing a whole role would deadlock the stream: fail fast, statically
    with pytest.raises(ValueError, match="kills every"):
        serve(ROLED, "kill:dc0@1;kill:dc1@2")
    # sharded dispatch has no pool-aware plane yet
    with pytest.raises(ValueError, match="single coordinator"):
        serve(ROLED + "/c2")
