"""Batched fleet serving at timing scale: EngineExecutor + FleetServer.

A model-free stub engine reproduces DecodeEngine's slot/step/heartbeat/cancel
bookkeeping (deterministic token function instead of a forward pass), so the
ISSUE acceptance numbers run in milliseconds in tier-1:

  - homogenization quality <= 1.3 under a mid-bundle perf-halving timeline,
  - exactly-once decode when requests migrate off a killed engine mid-bundle
    (partial tokens discarded, outputs equal the reference decode),
  - FleetServer admission control bounds per-replica queue depth per wave.

``tests/test_serve.py`` asserts the same invariants against real compiled
DecodeEngines in the slow tier.
"""

import pytest
from stub_engine import StubEngine, expected_tokens, mk_requests, stub_fleet

from repro.core import TimelineEvent
from repro.serve import EngineExecutor, FleetServer, Replica, Request


def test_batched_all_requests_decoded_correctly():
    srv, engines = stub_fleet([("a", 4.0, 4), ("b", 2.0, 2), ("c", 1.0, 1)])
    reqs = mk_requests(30, prompt_len=3, max_new=5)
    rep = srv.serve(reqs)
    assert rep.n_requests == 30
    for r in reqs:
        assert r.done and r.out_tokens == expected_tokens(r), r.rid
    # work split across every replica, proportional-ish to slot*clock rate
    shares = {n: sum(b.shares.get(n, 0) for b in rep.bundles) for n in engines}
    assert all(shares[n] > 0 for n in engines)
    assert shares["a"] > shares["c"]


# ------------------------------------------- mid-bundle perf-halving quality
def test_midbundle_perf_halving_quality_within_1_3():
    """Replica 'a' halves its step clock mid-bundle; migration of unstarted
    requests must keep the drain-time spread <= 1.3 (ISSUE acceptance)."""
    specs = [("a", 4.0, 2), ("b", 4.0, 2)]
    srv, _ = stub_fleet(specs, max_queue_depth=64)
    srv.serve(mk_requests(64))          # warm: heartbeats learn true rates
    # fleet rate ~ 8 slots-tokens/step-clock; fire the drop 20% into the wave
    reqs = mk_requests(64, prompt_len=2, max_new=6)
    est = sum(len(r.prompt) + r.max_new_tokens for r in reqs) / 16.0
    rep = srv.serve(
        reqs, timeline=(TimelineEvent(0.2 * est, "perf", "a", perf=2.0),)
    )
    assert rep.worst_quality <= 1.3, rep
    assert sum(b.n_migrated for b in rep.bundles) > 0
    for r in reqs:
        assert r.out_tokens == expected_tokens(r)


def test_tracker_learns_measured_batched_throughput():
    """Heartbeats are engine-measured: a 4-slot replica on the same step
    clock must learn ~4x the tokens/sec of a 1-slot replica, and the next
    wave's shares must follow."""
    srv, _ = stub_fleet([("wide", 2.0, 4), ("narrow", 2.0, 1)],
                      max_queue_depth=64)
    srv.serve(mk_requests(40, prompt_len=1, max_new=9))
    pv = srv.tracker.perf_vector()
    assert pv["wide"] > 2.5 * pv["narrow"], pv
    rep = srv.serve(mk_requests(40, prompt_len=1, max_new=9))
    shares = rep.bundles[0].shares
    assert shares["wide"] > 2 * shares["narrow"], shares


# ------------------------------------------------- exactly-once under a kill
def test_exactly_once_decode_migrating_off_killed_engine():
    """Kill a replica while it holds admitted (partially decoded) requests:
    the partial tokens are discarded via cancel(), the requests re-decode
    from scratch on survivors, and every output equals the reference."""
    specs = [("a", 2.0, 2), ("b", 2.0, 2), ("c", 2.0, 2)]
    srv, engines = stub_fleet(specs, max_queue_depth=64)
    reqs = mk_requests(36, prompt_len=2, max_new=8)
    est = sum(len(r.prompt) + r.max_new_tokens for r in reqs) / 12.0
    rep = srv.serve(reqs, timeline=(TimelineEvent(0.3 * est, "kill", "a"),))
    assert rep.n_requests == 36
    # the killed engine really was mid-decode: it produced tokens, and its
    # in-flight requests were withdrawn (no slot left occupied)
    assert engines["a"].tokens_out > 0
    assert engines["a"].active == 0 and not engines["a"].queue
    for r in reqs:
        assert r.done and r.out_tokens == expected_tokens(r), r.rid
    # sticky death: the next wave runs entirely on the survivors
    rep2 = srv.serve(mk_requests(12))
    assert "a" not in rep2.bundles[0].shares
    assert srv.live_replicas() == ["b", "c"]


def test_fleet_server_no_live_replicas_raises():
    srv, _ = stub_fleet([("a", 2.0, 2)])
    srv.kill("a")
    with pytest.raises(RuntimeError, match="no live replicas"):
        srv.serve(mk_requests(4))


# ------------------------------------------------------- admission control
def test_admission_control_bounds_queue_depth_per_wave():
    srv, _ = stub_fleet([("a", 2.0, 2), ("b", 2.0, 2)], max_queue_depth=3)
    reqs = mk_requests(20)
    rep = srv.serve(reqs)
    assert rep.n_requests == 20
    assert len(rep.bundles) == 4                    # ceil(20 / (3*2))
    assert [b.n_requests for b in rep.bundles] == [6, 6, 6, 2]
    for r in reqs:
        assert r.out_tokens == expected_tokens(r)


def test_admission_quota_shrinks_with_the_live_fleet():
    srv, _ = stub_fleet([("a", 2.0, 2), ("b", 2.0, 2)], max_queue_depth=4)
    srv.kill("b")
    rep = srv.serve(mk_requests(10))
    assert [b.n_requests for b in rep.bundles] == [4, 4, 2]
    assert all(set(b.shares) == {"a"} for b in rep.bundles)


def test_fleet_server_validates_construction():
    with pytest.raises(ValueError, match="without engines"):
        FleetServer([Replica("a", 1.0)], {})
    with pytest.raises(ValueError, match="max_queue_depth"):
        FleetServer([Replica("a", 1.0)], {"a": StubEngine()},
                    max_queue_depth=0)


def test_rejoin_brings_replica_back_with_fresh_engine():
    srv, _ = stub_fleet([("a", 2.0, 2), ("b", 2.0, 2)])
    srv.kill("a")
    with pytest.raises(KeyError, match="sticky"):
        srv.degrade("a", 1.0)
    srv.rejoin(Replica("a", 2.0), StubEngine(max_batch=2, name="a"),
               perf_prior=4.0)
    assert srv.live_replicas() == ["a", "b"]
    rep = srv.serve(mk_requests(16))
    assert sum(b.shares.get("a", 0) for b in rep.bundles) > 0


# ------------------------------------------------------- executor validation
def test_engine_executor_rejects_bad_bundles():
    reqs = mk_requests(4)
    with pytest.raises(ValueError, match="unique"):
        EngineExecutor({"a": StubEngine()}, reqs + [reqs[0]])
    busy = StubEngine()
    busy.submit(Request(rid=99, prompt=[1], max_new_tokens=2))
    with pytest.raises(ValueError, match="not idle"):
        EngineExecutor({"a": busy}, reqs)
    small = StubEngine(max_seq=4)
    with pytest.raises(ValueError, match="max_seq"):
        EngineExecutor({"a": StubEngine(), "b": small},
                       mk_requests(2, prompt_len=3, max_new=4))


# ------------------------------------------------- sticky-death fleet edits
def test_dispatcher_kill_prunes_replicas_and_degrade_raises():
    srv, _ = stub_fleet([("a", 4.0, 2), ("b", 4.0, 2)])
    srv.kill("b")
    assert set(srv.runtime.workers) == {"a"}        # no stale entry
    with pytest.raises(KeyError):
        srv.kill("b")                               # kills are sticky
    with pytest.raises(KeyError):
        srv.degrade("nope", 1.0)
    with pytest.raises(KeyError):
        srv.degrade("b", 1.0)                       # gone from the fleet
    srv.degrade("a", 2.0)
    assert srv.runtime.workers["a"].perf == 2.0


def test_dispatcher_timeline_kill_also_prunes_replicas():
    """A mid-wave timeline kill leaves the server's live fleet, the
    runtime's workers, without the dead replica (the old stale-entry bug of
    a mirrored replica table)."""
    srv, _ = stub_fleet([("a", 2.0, 2), ("b", 2.0, 2)], max_queue_depth=64)
    srv.serve(mk_requests(40), timeline=(TimelineEvent(1.0, "kill", "b"),))
    assert set(srv.runtime.workers) == {"a"}
    with pytest.raises(KeyError):
        srv.degrade("b", 1.0)
