"""Wall-clock execution backend behind the unified ExecutionBackend seam.

Tier-1 (small fleets, tiny grain counts — each wallclock run is a few dozen
sub-millisecond jitted calls):

  - seam neutrality: ``Cluster(backend='sim')`` is the default and produces
    field-for-field identical reports (the raw runtime likewise with an
    explicit ``SimBackend`` / ``ExecutionBackend``); only a ``measured``
    backend's step clock reaches a fleet's executors,
  - actionable validation: unknown ``backend`` / ``eta_mode`` strings and
    non-backend objects raise with the valid choices in the message,
  - wallclock smoke: measured speedup > 0, backend provenance on the report,
    ``metrics['wallclock']`` stats string, matmul values still exact,
  - seeded sim-vs-wallclock agreement on a tiny fleet (generous band — CI
    hosts are noisy; the tight band lives in the slow-tier bench test),
  - fault scenarios run under measurement (kill re-homes the dead worker's
    grains; serve rejects scenario+wallclock with an actionable error),
  - calibration: refit_profile's narrow measured band wins select_profile,
    save/load round-trips through JSON, the calibrate CLI's sim mode
    re-records a registered profile,
  - launcher plumbing: legacy fleet aliases warn exactly once per process,
    write_bench_json stamps the backend label.

Slow tier: the BENCH_wallclock flow end-to-end, asserting every case's
``rel_err`` is inside the artifact's stated ``agreement_band``.
"""

import json
import time
import warnings

import numpy as np
import pytest

from repro.cluster import Cluster, MatmulJob, SimJob
from repro.cluster.profiles import (
    get_profile,
    load_profiles,
    refit_profile,
    save_profiles,
    select_profile,
)
from repro.core import (
    AsyncRuntime,
    ExecutionBackend,
    SimBackend,
    SimWorker,
    WallclockBackend,
)

FLEET = "4:3:2:1"


# ---------------------------------------------------------------- seam: sim
def _report_fields(rep):
    return (
        rep.sim_time_s, rep.work_done, rep.predicted_speedup,
        rep.measured_speedup, rep.backend,
        tuple((p.sim_time_s, p.work, p.quality, p.n_migrated)
              for p in rep.phases),
    )


def test_sim_backend_is_default_and_identical():
    job = SimJob(size=64, n_jobs=2)
    sc = "halve:w0@50%"
    rep_default = Cluster(FLEET, priors="spec").simulate(job, scenario=sc)
    rep_explicit = Cluster(FLEET, priors="spec", backend="sim").simulate(
        job, scenario=sc)
    assert rep_default.backend == "sim"
    assert _report_fields(rep_default) == _report_fields(rep_explicit)


def test_raw_runtime_explicit_sim_backend_identical():
    # The extracted seam's null hypothesis: a base ExecutionBackend (and the
    # SimBackend subclass) reproduce the pre-seam logical clock exactly.
    def run(backend):
        workers = [SimWorker(f"w{i}", p) for i, p in enumerate((4, 3, 2, 1))]
        rt = AsyncRuntime(workers, backend=backend)
        return rt.run(40, grain_cost=1.0)

    t0, t1, t2 = (run(b).makespan
                  for b in (None, SimBackend(), ExecutionBackend()))
    assert t0 == t1 == t2


@pytest.mark.parametrize("make_backend, measured", [
    (lambda: None, False),
    (SimBackend, False),
    (ExecutionBackend, False),
    (lambda: WallclockBackend(calibration_reps=4), True),
], ids=["none", "sim", "base", "wallclock"])
def test_measured_backend_alone_sets_the_executor_step_clock(
        make_backend, measured):
    # ``measured`` is the one property that says durations are wall time;
    # only then do a fleet's executors read the backend's step clock.
    from stub_engine import mk_requests, stub_fleet

    backend = make_backend()
    srv, _ = stub_fleet([("pf", 2.0, 2), ("dc", 4.0, 2)], backend=backend)
    assert srv.runtime.backend.measured is measured
    roles = {"pf": "prefill", "dc": "decode"}
    for ex in (srv._executor(mk_requests(2)),
               srv._executor(mk_requests(2), roles=roles)):
        w = srv.runtime.workers["dc"]
        if measured:
            assert ex.step_clock == backend.step_clock
            assert ex.step_seconds(w) == backend.step_clock(w)
        else:
            assert ex.step_clock is None
            assert ex.step_seconds(w) == 1.0 / 4.0


def test_eta_mode_recompute_matches_incremental():
    job = SimJob(size=64)
    inc = Cluster(FLEET, eta_mode="incremental").simulate(job)
    rec = Cluster(FLEET, eta_mode="recompute").simulate(job)
    assert inc.sim_time_s == rec.sim_time_s


# ------------------------------------------------------------- validation
def test_unknown_backend_actionable():
    with pytest.raises(ValueError, match="wallclock"):
        Cluster(FLEET, backend="warp")
    with pytest.raises(TypeError, match="ExecutionBackend"):
        Cluster(FLEET, backend=42)


def test_unknown_eta_mode_actionable():
    with pytest.raises(ValueError, match="incremental"):
        Cluster(FLEET, eta_mode="exact")
    # None defers to $REPRO_ETA_MODE (runtime default) — valid.
    assert Cluster(FLEET, eta_mode=None).eta_mode is None


def test_serve_scenario_rejected_under_wallclock():
    from stub_engine import mk_requests

    from repro.cluster import ServeJob

    cluster = Cluster("2x2:1x2", backend="wallclock")
    with pytest.raises(ValueError, match="scenario"):
        cluster.serve(ServeJob(mk_requests(4)), scenario="halve:w0@50%")


# --------------------------------------------------------- wallclock smoke
def test_wallclock_repeats_emulate_heterogeneity():
    # Declared speed is emulated by work volume: base_repeats=12 keeps the
    # chain length integral for the canonical 4:3:2:1 fleet.
    wb = WallclockBackend(calibration_reps=4)
    assert [wb.repeats(1.0, p, 1.0) for p in (4, 3, 2, 1)] == [3, 4, 6, 12]
    # time_scale: wall seconds per modeled second, cost/perf-independent.
    assert wb.time_scale(2.0) == pytest.approx(12 * wb.unit_s / 2.0)
    assert wb.grain_seconds(1.0, 1.0, 1.0) == pytest.approx(12 * wb.unit_s)


def test_wallclock_simulate_smoke():
    rep = Cluster(FLEET, priors="spec", backend="wallclock").simulate(
        SimJob(size=48))
    assert rep.backend.startswith("wallclock")
    assert rep.measured_speedup > 0
    assert "wallclock/" in rep.metrics["wallclock"]
    assert rep.work_done == 48


def test_wallclock_shared_across_jobs():
    # The lazily-built backend is shared: one calibration, sticky devices.
    cluster = Cluster("2:1", backend="wallclock")
    r1 = cluster.simulate(SimJob(size=12))
    r2 = cluster.simulate(SimJob(size=12))
    assert r1.backend == r2.backend
    assert cluster._wallclock is not None
    assert cluster._wallclock.device_index("w0") == \
        cluster._wallclock.device_index("w0")


def test_wallclock_step_clock_starts_afresh_each_job():
    # A job's step clock is learned from its own ticks: a slow tick of the
    # last job (a compile, say) does not set the next job's first ticks.
    backend = WallclockBackend(calibration_reps=4)

    class Slow:
        # An executor wired to the backend's step clock, as the fleet
        # server wires its engine executors.
        uniform_cost = 1.0

        def tick(self, worker, now_s):
            time.sleep(0.05)
            return []

        def tick_s(self, worker, now_s):
            return backend.step_clock(worker)

    ex, w = Slow(), SimWorker("w0", 1.0)
    backend.begin_job(ex, 1, 0.0)
    backend.timed_tick(ex, w, 0.0)
    assert backend.step_clock(w) >= 0.05
    assert backend.tick_s(ex, w, 0.0) == backend.step_clock(w)
    backend.begin_job(ex, 1, 1.0)
    assert backend.step_clock(w) == backend.unit_s
    assert backend.tick_s(ex, w, 1.0) == backend.unit_s


def test_wallclock_matmul_values_exact():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((12, 8)).astype(np.float32)
    b = rng.standard_normal((8, 6)).astype(np.float32)
    rep = Cluster("2:1", backend="wallclock").simulate(MatmulJob(a, b))
    assert rep.backend.startswith("wallclock")
    assert rep.metrics["max_abs_err"] == 0.0


def test_wallclock_kill_scenario_conserves_work():
    rep = Cluster(FLEET, priors="spec", backend="wallclock").simulate(
        SimJob(size=48), scenario="kill:w0@50%")
    assert rep.work_done == 48
    assert rep.measured_speedup > 0


def test_wallclock_train_smoke():
    from repro.cluster import TrainJob
    from repro.models import LayerSpec, Model, ModelConfig

    cfg = ModelConfig(
        name="tiny", n_layers=1, d_model=16, n_heads=2, n_kv_heads=2,
        d_ff=32, vocab_size=32, head_dim=8,
        layer_pattern=(LayerSpec("attn", "dense"),),
        param_dtype="float32", compute_dtype="float32", use_pallas=False,
        rope_theta=1e4,
    )
    rep = Cluster("2:1", backend="wallclock").train(
        TrainJob(Model(cfg), steps=2, grains=4, seq_len=8))
    assert rep.backend.startswith("wallclock")
    assert np.isfinite(rep.phases[-1].metrics["loss"])


# ----------------------------------------------- sim-vs-wallclock agreement
def test_tiny_fleet_sim_wallclock_agreement():
    # Satellite: seeded agreement on a tiny fleet.  The band here is loose
    # (CI-shared cores jitter per-call times); the honest band assertion is
    # the slow-tier bench test below.
    job = SimJob(size=48)
    sim = Cluster("2:1", priors="spec", default_profile="local").simulate(job)
    wc = Cluster("2:1", priors="spec", backend="wallclock").simulate(job)
    pred = sim.predicted_speedup
    assert pred == pytest.approx(1.5, rel=1e-3)  # N_H of a 2:1 fleet
    assert abs(wc.measured_speedup - pred) / pred < 0.5
    assert wc.measured_speedup > 1.0            # beats the best solo worker


@pytest.mark.slow
def test_bench_wallclock_band():
    from benchmarks.bench_wallclock import run_bench

    result = run_bench(96)
    band = result["config"]["agreement_band"]
    for name, case in result["cases"].items():
        assert case["rel_err"] <= band, (
            f"{name}: wallclock measured {case['wallclock_measured']:.2f}x "
            f"vs sim predicted {case['sim_predicted']:.2f}x -> rel_err "
            f"{case['rel_err']:.1%} outside the stated {band:.0%} band"
        )
    assert result["agree"]


# ------------------------------------------------------------- calibration
def test_refit_profile_band_wins_selection():
    samples = [(100.0, 0.05), (200.0, 0.10), (400.0, 0.20)]
    prof = refit_profile("test-refit", samples, perf_band=(4.0, 6.0),
                         description="unit-test refit")
    try:
        assert prof.overhead_slope == pytest.approx(2000.0)
        # 5.0 is inside lan-1g's (3, 10) class band too; the measured
        # band is narrower, so the narrowest-covering rule prefers it.
        assert select_profile(5.0).name == "test-refit"
        assert select_profile(2.0).name == "paper-ethernet"
    finally:
        from repro.cluster import profiles as P

        P.PROFILES.pop("test-refit", None)


def test_save_load_profiles_roundtrip(tmp_path):
    path = tmp_path / "profiles.json"
    samples = [(10.0, 0.001), (20.0, 0.002)]
    refit_profile("test-rt", samples, perf_band=(100.0, 200.0))
    from repro.cluster import profiles as P

    try:
        save_profiles(path, ["test-rt"])
        src = get_profile("test-rt")
        P.PROFILES.pop("test-rt")
        loaded = load_profiles(path)
        assert [p.name for p in loaded] == ["test-rt"]
        back = get_profile("test-rt")
        assert back.calibration == src.calibration
        assert back.perf_band == src.perf_band
        assert back.overhead_slope == pytest.approx(src.overhead_slope)
    finally:
        P.PROFILES.pop("test-rt", None)


def test_calibrate_cli_sim_mode(tmp_path, capsys):
    from repro.launch.calibrate import main

    out = tmp_path / "cal.json"
    main(["--backend", "sim", "--name", "test-cal",
          "--loads", "100,200,400", "--out", str(out)])
    from repro.cluster import profiles as P

    try:
        prof = get_profile("test-cal")
        # Re-recorded modeled sweep refits to the source profile's slope.
        assert prof.overhead_slope == pytest.approx(
            get_profile(None).overhead_slope)
        assert out.exists()
        data = json.loads(out.read_text())
        assert data["profiles"][0]["name"] == "test-cal"
        assert "slope" in capsys.readouterr().out
    finally:
        P.PROFILES.pop("test-cal", None)


def test_calibrate_cli_needs_two_loads():
    from repro.launch.calibrate import main

    with pytest.raises(SystemExit, match="loads"):
        main(["--backend", "sim", "--loads", "100"])


# -------------------------------------------------------- launcher plumbing
def test_fleet_alias_warns_once_per_process():
    import argparse

    from repro.launch import common

    common._warned_aliases.discard("--pods")
    ap = argparse.ArgumentParser()
    common.add_fleet_arg(ap, legacy="--pods", default="1", help="fleet")
    with pytest.warns(DeprecationWarning, match="--pods is deprecated"):
        args = ap.parse_args(["--pods", "4:2"])
    assert args.fleet == "4:2"
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # second use: no warning
        assert ap.parse_args(["--pods", "3:1"]).fleet == "3:1"
        assert ap.parse_args(["--fleet", "2:1"]).fleet == "2:1"


def test_backend_args_and_env(monkeypatch):
    import argparse

    from repro.launch.common import add_backend_args, apply_env

    ap = argparse.ArgumentParser()
    add_backend_args(ap)
    args = ap.parse_args(["--backend", "wallclock"])
    monkeypatch.setenv("XLA_FLAGS", "")
    monkeypatch.delenv("REPRO_TUNED", raising=False)
    apply_env(args, n_workers=3)
    import os

    assert "--xla_force_host_platform_device_count=3" in \
        os.environ["XLA_FLAGS"]
    # sim backend with no --devices: no pinning.
    monkeypatch.setenv("XLA_FLAGS", "")
    apply_env(ap.parse_args([]), n_workers=3)
    assert "host_platform" not in os.environ["XLA_FLAGS"]


def test_write_bench_json_backend_stamp(tmp_path):
    from benchmarks.run import write_bench_json

    path = tmp_path / "BENCH_x.json"
    stamped = write_bench_json(str(path), {"v": 1},
                               backend="wallclock[4d]")
    assert stamped["provenance"]["backend"] == "wallclock[4d]"
    assert json.loads(path.read_text())["provenance"]["backend"] == \
        "wallclock[4d]"
    # Default stamp stays "sim" so existing bench writers are unchanged.
    stamped = write_bench_json(str(path), {"v": 1})
    assert stamped["provenance"]["backend"] == "sim"
