"""Serving tests: continuous-batching engine correctness + homogenized dispatch,
and the serving path's spans."""

import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from stub_engine import mk_requests, stub_fleet

from repro.cluster import Cluster, ServeJob
from repro.core import TimelineEvent
from repro.models import LayerSpec, Model, ModelConfig, MoEConfig
from repro.obs import Tracer
from repro.obs import trace as obs_trace
from repro.serve import (
    DecodeEngine,
    FleetServer,
    Replica,
    Request,
)
from repro.serve.engine import greedy_token


def tiny_model(moe=False):
    cfg = ModelConfig(
        name="tiny-serve", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=64, vocab_size=64, head_dim=16,
        layer_pattern=(LayerSpec("attn", "moe" if moe else "dense"),),
        moe=MoEConfig(n_routed=4, top_k=2, d_expert=32, capacity_factor=4.0)
        if moe else None,
        param_dtype="float32", compute_dtype="float32", use_pallas=False,
        rope_theta=1e4,
    )
    m = Model(cfg)
    return m, m.init(jax.random.key(0))


def _greedy_reference(model, params, prompt, n_new, max_seq):
    """Reference: full-context greedy decode via repeated full forward."""
    toks = list(prompt)
    for _ in range(n_new):
        batch = {
            "tokens": jnp.asarray([toks], jnp.int32),
            "targets": jnp.zeros((1, len(toks)), jnp.int32),
            "loss_mask": jnp.ones((1, len(toks)), jnp.float32),
        }
        logits, _ = model.logits(params, batch)
        toks.append(int(np.asarray(logits)[0, -1, : model.cfg.vocab_size].argmax()))
    return toks[len(prompt):]


def test_engine_matches_full_forward_greedy():
    model, params = tiny_model()
    eng = DecodeEngine(model, params, max_batch=2, max_seq=32)
    prompt = [3, 14, 15, 9, 2]
    req = Request(rid=0, prompt=prompt, max_new_tokens=6)
    eng.submit(req)
    done = eng.run_until_drained()
    assert len(done) == 1 and done[0].done
    ref = _greedy_reference(model, params, prompt, 6, 32)
    assert done[0].out_tokens == ref, (done[0].out_tokens, ref)


def test_engine_continuous_batching_multiple_lengths():
    model, params = tiny_model()
    eng = DecodeEngine(model, params, max_batch=2, max_seq=48)
    reqs = [
        Request(rid=i, prompt=[1 + i, 7, 3 + i], max_new_tokens=3 + i)
        for i in range(5)
    ]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    assert len(done) == 5
    for r in done:
        ref = _greedy_reference(model, params, r.prompt, r.max_new_tokens, 48)
        assert r.out_tokens == ref, (r.rid, r.out_tokens, ref)


def test_engine_slot_recycling_isolated():
    """A recycled slot must produce the same output as a fresh engine."""
    model, params = tiny_model()
    eng = DecodeEngine(model, params, max_batch=1, max_seq=32)
    eng.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=4))
    eng.run_until_drained()
    eng.submit(Request(rid=1, prompt=[9, 2], max_new_tokens=4))
    out2 = eng.run_until_drained()[0].out_tokens
    fresh = DecodeEngine(model, params, max_batch=1, max_seq=32)
    fresh.submit(Request(rid=1, prompt=[9, 2], max_new_tokens=4))
    ref = fresh.run_until_drained()[0].out_tokens
    assert out2 == ref


def test_engine_moe_model():
    model, params = tiny_model(moe=True)
    eng = DecodeEngine(model, params, max_batch=2, max_seq=24)
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    done = eng.run_until_drained()
    assert len(done[0].out_tokens) == 4


def test_engine_eos_stops():
    model, params = tiny_model()
    # find the first greedy token and use it as "eos"
    ref = _greedy_reference(model, params, [4, 5], 1, 16)
    eng = DecodeEngine(model, params, max_batch=1, max_seq=16, eos_id=ref[0])
    eng.submit(Request(rid=0, prompt=[4, 5], max_new_tokens=8))
    done = eng.run_until_drained()
    assert done[0].out_tokens == ref


# ------------------------------------------------------------------- dispatch
def padded_model():
    """``tiny_model`` with a vocabulary of 61 padded to 64."""
    m, _ = tiny_model()
    cfg = dataclasses.replace(m.cfg, vocab_size=61, vocab_pad_to=64)
    assert cfg.padded_vocab > cfg.vocab_size
    m = Model(cfg)
    return m, m.init(jax.random.key(1))


def test_greedy_token_skips_the_vocabulary_padding():
    logits = jnp.asarray([[0.0, 2.0, 2.0, 9.0], [3.0, 1.0, 3.0, 0.0]])
    tokens = greedy_token(logits, 3)
    assert tokens.dtype == jnp.int32
    assert tokens.tolist() == [1, 0]        # the first of equal maxima


def test_engine_step_tokens_are_the_host_argmax():
    """Each step's device tokens are, bitwise, the host argmax over the true
    vocabulary of ``Model.decode_step``'s logits for the same inputs, in
    every slot, feeding or sampling; only those int32 are fetched."""
    model, params = padded_model()
    vocab = model.cfg.vocab_size
    eng = DecodeEngine(model, params, max_batch=3, max_seq=32)
    eng.tracer = Tracer()
    logits_of = jax.jit(model.decode_step)
    run = eng._decode
    host, device = [], []

    def recording(model_, params_, caches, toks, pos):
        logits, _ = logits_of(params_, jax.tree.map(jnp.copy, caches), toks, pos)
        host.append(np.asarray(logits, np.float32)[:, 0, :vocab].argmax(-1))
        tokens, caches = run(model_, params_, caches, toks, pos)
        device.append(np.asarray(tokens))
        return tokens, caches

    eng._decode = recording
    reqs = [Request(rid=i, prompt=[(7 * i + j) % vocab for j in range(1 + 3 * i)],
                    max_new_tokens=2 + i) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == list(range(5))
    assert len(device) == eng.steps > 0
    for h, d in zip(host, device, strict=True):
        assert d.dtype == np.int32 and d.shape == (3,)
        np.testing.assert_array_equal(d, h)
    steps = [s for s in eng.tracer.spans if s.name == "engine.step"]
    assert any(s.attrs["feeding"] and s.attrs["sampled"] for s in steps)
    fetch = [s for s in eng.tracer.spans if s.name == "engine.step.fetch"]
    assert len(fetch) == eng.steps
    assert all(s.attrs["bytes"] == 4 * 3 for s in fetch)
    for r in reqs:
        assert r.out_tokens == _greedy_reference(model, params, r.prompt,
                                                 r.max_new_tokens, 32)


@pytest.mark.parametrize("length", [5, 8])
def test_prefill_first_token_is_the_host_argmax(length):
    """``prefill``'s first token is the host argmax over the true vocabulary
    of ``Model.prefill``'s logits at ``last_pos``; ``insert`` starts the
    request from it and decodes on as the full forward does."""
    model, params = padded_model()
    vocab = model.cfg.vocab_size
    prompt = [(5 * j + 3) % vocab for j in range(length)]
    eng = DecodeEngine(model, params, max_batch=2, max_seq=32)
    req = Request(rid=0, prompt=prompt, max_new_tokens=4)
    handoff = eng.prefill(req)
    toks = np.zeros((1, handoff.bucket), np.int32)
    toks[0, :length] = prompt
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(toks)},
                              last_pos=jnp.int32(length - 1))
    assert handoff.first_token == int(
        np.asarray(logits, np.float32)[0, 0, :vocab].argmax())
    assert eng.insert(handoff) >= 0
    assert req.out_tokens == [handoff.first_token]
    eng.run_until_drained()
    assert req.out_tokens == _greedy_reference(model, params, prompt, 4, 32)


def test_dispatch_proportional_after_learning():
    """From neutral priors, heartbeats teach the tracker the replicas' step
    clocks: after a few waves the allotment follows them."""
    srv, _ = stub_fleet([("fast", 10.0, 1), ("slow", 2.0, 1)],
                        max_queue_depth=120)
    rep = None
    for _ in range(6):
        rep = srv.serve(mk_requests(120))
    shares = rep.bundles[0].shares
    assert shares["fast"] > 4 * shares["slow"]


def test_dispatch_homogenized_beats_equal_makespan():
    specs = [("a", 10.0, 1), ("b", 5.0, 1), ("c", 1.0, 1)]
    dh, _ = stub_fleet(specs, homogenize=True, max_queue_depth=160)
    de, _ = stub_fleet(specs, homogenize=False, max_queue_depth=160)
    for _ in range(5):
        rh = dh.serve(mk_requests(160))
        re_ = de.serve(mk_requests(160))
    assert rh.sim_time_s < re_.sim_time_s
    # homogenization line: drain times nearly equal across replicas
    ts = [t for t in rh.bundles[0].worker_busy.values() if t > 0]
    assert max(ts) / min(ts) < 1.25


def test_dispatch_replica_failure():
    srv, _ = stub_fleet([("a", 4.0, 1), ("b", 4.0, 1)], max_queue_depth=64)
    srv.serve(mk_requests(64))
    srv.kill("b")
    rep = srv.serve(mk_requests(64))
    assert rep.bundles[0].shares == {"a": 64}


def test_dispatch_midbundle_degradation_rehomogenizes():
    """A replica degrading *during* a bundle: the runtime migrates its queued
    requests, so the bundle still drains near the homogenization line."""
    srv, _ = stub_fleet([("a", 4.0, 1), ("b", 4.0, 1)], max_queue_depth=400)
    for _ in range(3):
        srv.serve(mk_requests(160))  # learn true perfs
    # 400 requests of 7 steps at 8 steps/s plan 350 s: the drop lands 10% in
    rep = srv.serve(
        mk_requests(400), timeline=(TimelineEvent(35.0, "perf", "b", perf=1.0),)
    )
    res = rep.bundles[0]
    assert res.n_migrated > 0
    assert res.quality <= 1.1, res
    assert res.shares["a"] > res.shares["b"]


@pytest.mark.slow  # compiles two engines; covered by the slow tier
def test_batched_fleet_real_engines_match_reference():
    """The batched EngineExecutor path on real engines: slots stay batched,
    heartbeats are measured, and every output still equals the single-engine
    greedy reference."""
    model, params = tiny_model()
    replicas = [Replica("fast", 4.0), Replica("slow", 1.0)]
    engines = {
        "fast": DecodeEngine(model, params, max_batch=4, max_seq=32, name="fast"),
        "slow": DecodeEngine(model, params, max_batch=2, max_seq=32, name="slow"),
    }
    srv = FleetServer(replicas, engines, max_queue_depth=16)
    reqs = [Request(rid=i, prompt=[1 + i % 5, 7, 2], max_new_tokens=4)
            for i in range(12)]
    rep = srv.serve(reqs)
    assert rep.n_requests == 12 and rep.tokens_out == 48
    for r in reqs:
        ref = _greedy_reference(model, params, r.prompt, 4, 32)
        assert r.out_tokens == ref, (r.rid, r.out_tokens, ref)
    # the wide+fast replica carried most of the bundle
    shares = rep.bundles[0].shares
    assert shares["fast"] > shares["slow"]


@pytest.mark.slow  # compiles three engines; covered by the slow tier
def test_batched_fleet_real_engines_exactly_once_under_kill():
    """Mid-bundle kill on real engines: admitted requests are withdrawn from
    the dead engine (decode state reset) and re-decoded from scratch on the
    survivors — outputs bitwise equal the never-killed reference."""
    model, params = tiny_model()
    replicas = [Replica(n, 2.0) for n in ("a", "b", "c")]
    engines = {
        n: DecodeEngine(model, params, max_batch=2, max_seq=32, name=n)
        for n in ("a", "b", "c")
    }
    srv = FleetServer(replicas, engines, max_queue_depth=16)
    reqs = [Request(rid=i, prompt=[2 + i % 6, 3], max_new_tokens=5)
            for i in range(12)]
    # ~84 token-units over ~6 slot-tokens/sec: kill 30% into the bundle
    rep = srv.serve(reqs, timeline=(TimelineEvent(4.0, "kill", "a"),))
    assert rep.n_requests == 12
    assert engines["a"].active == 0 and not engines["a"].queue
    for r in reqs:
        ref = _greedy_reference(model, params, r.prompt, 5, 32)
        assert r.out_tokens == ref, (r.rid, r.out_tokens, ref)
    assert srv.live_replicas() == ["b", "c"]


def test_engine_heartbeat_reports_throughput():
    model, params = tiny_model()
    eng = DecodeEngine(model, params, max_batch=2, max_seq=32, name="e0")
    assert eng.heartbeat(0.0) is None          # no steps yet
    eng.submit(Request(rid=0, prompt=[3, 4], max_new_tokens=5))
    eng.run_until_drained()
    hb = eng.heartbeat(1.0)
    assert hb is not None and hb.worker == "e0"
    # work counts prompt tokens consumed as well as output tokens
    assert hb.throughput == pytest.approx(
        (eng.tokens_out + eng.prompt_fed) / eng.steps)
    assert eng.heartbeat(2.0) is None          # nothing new since last report


def test_engine_heartbeat_counts_prompt_feed_no_ema_distortion():
    """Steps that only consumed prompt tokens are real engine work: the
    heartbeat reports them at the engine's true speed instead of going
    silent (silence froze the tracker's perf estimate exactly when a new
    bundle landed — the early-estimate distortion) and the follow-up report
    covers only the interval since."""
    model, params = tiny_model()
    eng = DecodeEngine(model, params, max_batch=1, max_seq=32, name="e0")
    eng.submit(Request(rid=0, prompt=[3, 14, 15, 9, 2], max_new_tokens=3))
    eng.step()
    eng.step()                                 # 2 steps in, still mid-prompt
    assert eng.tokens_out == 0 and eng.steps == 2
    fed = eng.prompt_fed
    hb = eng.heartbeat(1.0)
    assert hb is not None and fed > 0
    assert hb.work_done == float(fed)
    eng.run_until_drained()
    hb = eng.heartbeat(2.0, seconds_per_step=0.5)
    assert hb is not None
    # only the new interval: the mid-prompt report consumed its steps
    assert hb.work_done == float(eng.tokens_out + eng.prompt_fed - fed)
    assert hb.elapsed_s == pytest.approx((eng.steps - 2) * 0.5)


def test_engine_cancel_resets_decode_state():
    """cancel() mid-decode discards partial tokens; re-submitting to a fresh
    engine produces the same output as never having started (exactly-once
    decode under migration)."""
    model, params = tiny_model()
    eng = DecodeEngine(model, params, max_batch=1, max_seq=32, name="e0")
    req = Request(rid=7, prompt=[3, 14, 15], max_new_tokens=4)
    eng.submit(req)
    for _ in range(4):
        eng.step()                             # prompt fed + 2 tokens out
    assert len(req.out_tokens) == 2 and not req.done
    got = eng.cancel(7)
    assert got is req and req.out_tokens == [] and not req.done
    assert eng.active == 0 and eng.cancel(7) is None     # idempotent
    eng2 = DecodeEngine(model, params, max_batch=1, max_seq=32, name="e1")
    eng2.submit(req)
    eng2.run_until_drained()
    ref = _greedy_reference(model, params, [3, 14, 15], 4, 32)
    assert req.out_tokens == ref


# ----------------------------------------------------------- serving spans
SPAN_FLEETS = {"mixed": "a=2x4,b=1x2", "disagg": "p=2.0^prefill,d=1.0x4^decode"}


def _span_requests():
    return [Request(rid=i, prompt=[1 + (3 * i) % 11, 7, 2, 5][: 2 + i % 3],
                    max_new_tokens=2 + i % 4)
            for i in range(10)]


def _cluster_serve(kind, model, params, tracer):
    reqs = _span_requests()
    Cluster(SPAN_FLEETS[kind], backend="wallclock", trace=tracer).serve(
        ServeJob(reqs, model=model, params=params, max_seq=32,
                 max_queue_depth=2))
    return reqs


@pytest.fixture(scope="module")
def span_runs():
    """Each fleet kind served once traced and once untraced, through
    Cluster.serve on the wall-clock backend (so waves, ticks and engine
    phases all run)."""
    model, params = tiny_model()
    out = {}
    for kind in SPAN_FLEETS:
        tracer = Tracer()
        traced = _cluster_serve(kind, model, params, tracer)
        out[kind] = (tracer, traced, _cluster_serve(kind, model, params, None))
    return out


@pytest.mark.parametrize("kind", SPAN_FLEETS)
def test_spans_nest_inside_their_parents(span_runs, kind):
    tracer = span_runs[kind][0]
    by_id = {s.id: s for s in tracer.spans}
    assert all(s.t1_ns is not None and s.t1_ns >= s.t0_ns for s in tracer.spans)
    children = {}
    for s in tracer.spans:
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.t0_ns <= s.t0_ns and s.t1_ns <= p.t1_ns, (p.name, s.name)
            assert s.worker == p.worker or p.worker is None
            children.setdefault(p.id, []).append(s)
    for kids in children.values():
        kids.sort(key=lambda s: s.t0_ns)
        for a, b in zip(kids, kids[1:]):
            assert a.t1_ns <= b.t0_ns, (a.name, b.name)
    parent_name = {s.name: by_id[s.parent].name if s.parent else None
                   for s in tracer.spans}
    assert parent_name["engine.step"] == "runtime.tick"
    assert parent_name["engine.step.fetch"] == "engine.step"
    # A disaggregated fleet serves the pool as one open-loop stream: one wave.
    assert parent_name["runtime.tick"] == "serve.wave"
    if kind == "disagg":
        assert parent_name["engine.prefill"] == "runtime.tick"
        assert parent_name["engine.prefill.device"] == "engine.prefill"
    # Every step that ran the device has its four phases.
    for s in tracer.spans:
        if s.name == "engine.step" and s.attrs["active"]:
            names = [k.name for k in children[s.id]]
            assert names == ["engine.step.prep", "engine.step.device",
                             "engine.step.fetch", "engine.step.sample"]


@pytest.mark.parametrize("kind", SPAN_FLEETS)
def test_every_request_has_its_wait_spans(span_runs, kind):
    tracer, reqs, _ = span_runs[kind]
    names = {}
    for s in tracer.spans:
        if s.keyed:
            names.setdefault(s.rid, []).append(s.name)
    want = (["request.backlog", "request.queue"] if kind == "mixed"
            else ["request.queue", "request.handoff"])
    assert {r.rid: names[r.rid] for r in reqs} == {r.rid: want for r in reqs}
    for s in tracer.spans:
        if s.name in ("request.queue", "request.handoff"):
            assert s.worker in ("a", "b", "p", "d")
    if kind == "mixed":
        # Two requests a replica a wave: the fleet needed several waves.
        assert sum(s.name == "serve.wave" for s in tracer.spans) == 3


def test_disagg_spans_carry_handoff_bytes(span_runs):
    """Each ``engine.prefill`` span names the bytes of the handoff it made,
    the same per cache position at every bucket; the stream's ``serve.wave``
    carries the most handoff bytes the executor held at once."""
    tracer = span_runs["disagg"][0]
    prefills = [s.attrs for s in tracer.spans if s.name == "engine.prefill"]
    assert len(prefills) == len(_span_requests())
    per_pos = {a["handoff_bytes"] / a["bucket"] for a in prefills}
    assert len(per_pos) == 1 and per_pos.pop() > 0
    (wave,) = [s.attrs for s in tracer.spans if s.name == "serve.wave"]
    sizes = [a["handoff_bytes"] for a in prefills]
    assert max(sizes) <= wave["handoff_bytes_peak"] <= sum(sizes)


@pytest.mark.parametrize("kind", SPAN_FLEETS)
def test_step_counters_add_up_to_the_decoded_tokens(span_runs, kind):
    tracer, reqs, _ = span_runs[kind]
    steps = [s for s in tracer.spans if s.name == "engine.step"]
    decoded = sum(len(r.out_tokens) for r in reqs)
    if kind == "disagg":
        decoded -= len(reqs)            # each first token came from prefill
    assert sum(s.attrs["sampled"] for s in steps) == decoded
    for s in steps:
        a = s.attrs
        assert a["active"] == a["feeding"] + a["sampled"] <= a["max_batch"]
    fetch = [s for s in tracer.spans if s.name == "engine.step.fetch"]
    max_batch = {s.id: s.attrs["max_batch"] for s in steps}
    assert fetch and all(s.attrs["bytes"] == 4 * max_batch[s.parent] for s in fetch)


@pytest.mark.parametrize("kind", SPAN_FLEETS)
def test_traced_serve_tokens_bitwise_identical(span_runs, kind):
    _, traced, untraced = span_runs[kind]
    assert [r.out_tokens for r in traced] == [r.out_tokens for r in untraced]


@pytest.mark.parametrize("kind", SPAN_FLEETS)
def test_untraced_serve_builds_no_span(monkeypatch, kind):
    def refuse(*a, **k):
        raise AssertionError("a span was entered with no tracer")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(obs_trace.Span, "__init__", refuse)
    model, params = tiny_model()
    reqs = _cluster_serve(kind, model, params, None)
    assert all(len(r.out_tokens) == r.max_new_tokens for r in reqs)


def test_profiler_annotations_match_step_spans(tmp_path):
    """The spans' annotations land on the profiler's host plane: one
    ``repro.engine.step`` per in-memory ``engine.step`` span, each as long
    within 50 microseconds."""
    model, params = tiny_model()
    tracer = Tracer()
    _cluster_serve("mixed", model, params, Tracer())      # compile first
    jax.profiler.start_trace(str(tmp_path))
    try:
        _cluster_serve("mixed", model, params, tracer)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    annotated = sorted(
        (e.start_ns, e.duration_ns)
        for plane in jax.profiler.ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name == "repro.engine.step")
    spans = [s for s in tracer.spans if s.name == "engine.step"]
    assert len(annotated) == len(spans) > 0
    for (_, dur), s in zip(annotated, spans):
        assert abs(dur - (s.t1_ns - s.t0_ns)) <= 50_000
