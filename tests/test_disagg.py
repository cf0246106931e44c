"""Prefill/decode disaggregation: bucketed prefill kernel, KV handoff,
role-split fleet serving.

Three layers, mirroring the stack:

  - kernels/prefill: length buckets, fused interpret-mode kernel vs the jnp
    oracle, cache-dtype cast, end-padding exactness (causality keeps valid
    rows bitwise-independent of pad content),
  - serve/engine: ``prefill() -> KVHandoff`` reproduces the teacher-forced
    submit path bitwise; ``insert()`` continuation, re-insert after cancel
    (the exactly-once contract), slot exhaustion, finished-at-prefill,
  - serve/fleet + cluster: role-split streams at timing scale with stub
    engines — pool separation, TTFT split, per-role quality, and the
    double-kill scenario (prefill replica mid-prefill AND decode replica
    mid-decode) completing every request exactly once, tokens bitwise equal
    to the single-engine reference, no leaked slots.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from stub_engine import StubEngine, expected_tokens, mk_requests

from repro.cluster import Cluster, ServeJob, WorkerSpec
from repro.core import TimelineEvent
from repro.kernels.prefill.ops import length_bucket, prefill_attention
from repro.models import LayerSpec, Model, ModelConfig
from repro.serve import DecodeEngine, FleetServer, Replica, Request

RNG = np.random.default_rng(7)


def stub_factory(spec: WorkerSpec) -> StubEngine:
    return StubEngine(max_batch=spec.concurrency, max_seq=256, name=spec.name)


def tiny_model():
    cfg = ModelConfig(
        name="tiny-disagg", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=64, vocab_size=64, head_dim=16,
        layer_pattern=(LayerSpec("attn", "dense"),),
        param_dtype="float32", compute_dtype="float32", use_pallas=False,
        rope_theta=1e4,
    )
    m = Model(cfg)
    return m, m.init(jax.random.key(0))


# ==================================================================== spec
def test_fleet_spec_role_grammar_round_trip():
    from repro.cluster import FleetSpec

    fleet = FleetSpec.parse("fast=2.0^prefill, 1.0x4^decode*2")
    assert fleet.has_roles
    assert [w.role for w in fleet.workers] == ["prefill", "decode", "decode"]
    fleet.validate_roles()
    again = FleetSpec.parse(str(fleet))
    assert [(w.name, w.perf, w.concurrency, w.role) for w in again.workers] \
        == [(w.name, w.perf, w.concurrency, w.role) for w in fleet.workers]
    assert not FleetSpec.parse("4:2").has_roles


def test_fleet_spec_unknown_role_rejected():
    from repro.cluster import FleetSpec

    with pytest.raises(ValueError, match="role"):
        FleetSpec.parse("a=1^encode,b=1^decode")


# ================================================================= kernels
def test_length_bucket_ladder():
    assert length_bucket(1, 128) == 16
    assert length_bucket(16, 128) == 16
    assert length_bucket(17, 128) == 32
    assert length_bucket(100, 128) == 128
    # clamped to max_seq even when the pow2 rung would overshoot
    assert length_bucket(40, 48) == 48
    with pytest.raises(ValueError, match="exceeds max_seq"):
        length_bucket(129, 128)


@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2), (8, 2)])
def test_prefill_kernel_matches_ref(hq, hkv):
    b, s, d = 1, 32, 16
    q = jnp.asarray(RNG.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    out, kc, vc = prefill_attention(
        q, k, v, use_pallas=True, interpret=True, block_q=16, block_k=16)
    ref, kr, vr = prefill_attention(q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-4, atol=5e-5)
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vc), np.asarray(vr))


def test_prefill_cache_dtype_cast():
    b, s, h, d = 1, 16, 2, 16
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    out, kc, vc = prefill_attention(
        q, k, v, cache_dtype=jnp.bfloat16,
        use_pallas=True, interpret=True, block_q=16, block_k=16)
    assert out.dtype == jnp.float32
    assert kc.dtype == vc.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(kc, np.float32), np.asarray(k.astype(jnp.bfloat16), np.float32))


def test_prefill_end_padding_is_exact():
    """Causal masking makes rows [0, L) independent of the pad tail — the
    property `DecodeEngine.prefill` relies on to read true last-token logits
    from a bucket-padded prompt."""
    b, s, h, d, L = 1, 32, 2, 16, 20
    q = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, h, d)), jnp.float32)
    padded, _, _ = prefill_attention(
        q, k, v, use_pallas=True, interpret=True, block_q=16, block_k=16)
    exact, _, _ = prefill_attention(
        q[:, :L], k[:, :L], v[:, :L], use_pallas=False)
    np.testing.assert_allclose(np.asarray(padded[:, :L]), np.asarray(exact),
                               rtol=5e-4, atol=5e-5)


# ================================================================== engine
def test_engine_prefill_insert_matches_submit_path():
    """prefill -> handoff -> insert on a *different* engine reproduces the
    continuous-batching submit path bitwise, first token included."""
    model, params = tiny_model()
    prompt = list(RNG.integers(0, 64, 20))

    ref_req = Request(rid=0, prompt=list(prompt), max_new_tokens=6)
    ref_eng = DecodeEngine(model, params, max_batch=2, max_seq=64)
    ref_eng.submit(ref_req)
    ref_eng.run_until_drained()

    pf = DecodeEngine(model, params, max_batch=1, max_seq=64, name="pf")
    dc = DecodeEngine(model, params, max_batch=2, max_seq=64, name="dc")
    req = Request(rid=1, prompt=list(prompt), max_new_tokens=6)
    handoff = pf.prefill(req)
    assert handoff.pos == len(prompt)
    assert handoff.bucket == length_bucket(len(prompt), 64)
    assert handoff.first_token == ref_req.out_tokens[0]
    assert dc.insert(handoff) >= 0
    dc.run_until_drained()
    assert req.out_tokens == ref_req.out_tokens


def test_engine_reinsert_after_cancel_is_bitwise():
    """The exactly-once contract: a decode cancelled mid-stream re-inserts
    the *same* retained handoff on an heir and completes bitwise-identically
    — no re-prefill, no double-counted tokens."""
    model, params = tiny_model()
    prompt = list(RNG.integers(0, 64, 18))
    ref_req = Request(rid=0, prompt=list(prompt), max_new_tokens=8)
    ref_eng = DecodeEngine(model, params, max_batch=1, max_seq=64)
    ref_eng.submit(ref_req)
    ref_eng.run_until_drained()

    pf = DecodeEngine(model, params, max_batch=1, max_seq=64, name="pf")
    dc0 = DecodeEngine(model, params, max_batch=1, max_seq=64, name="dc0")
    dc1 = DecodeEngine(model, params, max_batch=1, max_seq=64, name="dc1")
    req = Request(rid=1, prompt=list(prompt), max_new_tokens=8)
    handoff = pf.prefill(req)
    dc0.insert(handoff)
    for _ in range(3):          # partial decode, then the replica "dies"
        dc0.step()
    assert not req.done
    dc0.cancel(req.rid)
    assert dc0.active == 0
    dc1.insert(handoff)
    dc1.run_until_drained()
    assert req.done
    assert req.out_tokens == ref_req.out_tokens


def test_engine_insert_finished_at_prefill_needs_no_slot():
    model, params = tiny_model()
    pf = DecodeEngine(model, params, max_batch=1, max_seq=64)
    dc = DecodeEngine(model, params, max_batch=1, max_seq=64)
    req = Request(rid=0, prompt=[3, 5, 7], max_new_tokens=1)
    handoff = pf.prefill(req)
    assert dc.insert(handoff) == -1
    assert req.done and req.out_tokens == [handoff.first_token]
    assert dc.active == 0


def test_engine_insert_slot_exhaustion_raises():
    model, params = tiny_model()
    pf = DecodeEngine(model, params, max_batch=1, max_seq=64)
    dc = DecodeEngine(model, params, max_batch=1, max_seq=64)
    h0 = pf.prefill(Request(rid=0, prompt=[1, 2], max_new_tokens=4))
    h1 = pf.prefill(Request(rid=1, prompt=[3, 4], max_new_tokens=4))
    assert dc.insert(h0) == 0
    with pytest.raises(RuntimeError, match="no free slot"):
        dc.insert(h1)


def test_engine_prefill_validates_inputs():
    model, params = tiny_model()
    eng = DecodeEngine(model, params, max_batch=1, max_seq=32)
    with pytest.raises(ValueError, match="non-empty"):
        eng.prefill(Request(rid=0, prompt=[], max_new_tokens=4))
    with pytest.raises(ValueError, match="max_seq"):
        eng.prefill(Request(rid=1, prompt=list(range(30)), max_new_tokens=8))


# =========================================================== fleet (stubs)
def mk_roled_fleet(n_prefill=1, n_decode=2, max_batch=4):
    reps = ([Replica(f"pf{i}", 2.0) for i in range(n_prefill)]
            + [Replica(f"dc{i}", 1.0) for i in range(n_decode)])
    engines = {r.name: StubEngine(max_batch=max_batch, max_seq=256,
                                  name=r.name) for r in reps}
    roles = {r.name: ("prefill" if r.name.startswith("pf") else "decode")
             for r in reps}
    return reps, engines, roles


def test_stream_disagg_bitwise_and_pool_separation():
    reps, engines, roles = mk_roled_fleet()
    srv = FleetServer(reps, engines, max_queue_depth=8)
    reqs = mk_requests(6, prompt_len=20, max_new=8)
    rep = srv.serve_stream(reqs, [0.1 * i for i in range(6)], roles=roles)

    assert rep.n_served == 6 and rep.n_shed == 0
    assert rep.n_handoffs == 6
    for r in reqs:
        assert r.out_tokens == expected_tokens(r), r.rid
    # decode grains land on the decode pool; prefill pool only feeds prompts
    assert all(t.worker in ("dc0", "dc1") for t in rep.traces)
    assert engines["pf0"].handoffs_in == 0
    assert engines["pf0"].prompt_fed == 6 * 20
    assert engines["dc0"].handoffs_in + engines["dc1"].handoffs_in == 6
    for name, eng in engines.items():
        assert eng.active == 0, (name, eng.active)
    # all four TTFT components present, non-negative, over every request
    split = rep.ttft_split.as_dict()
    assert split["n"] == 6
    for key in ("queue_s", "prefill_s", "handoff_s", "decode_s"):
        assert split[key]["mean"] >= 0, (key, split)
    assert {rs.role for rs in rep.role_stats} == {"prefill", "decode"}


def test_stream_disagg_double_kill_exactly_once():
    """Kill the prefill replica mid-prefill AND a decode replica mid-decode
    in one stream: every request still completes exactly once, tokens
    bitwise equal to the single-engine reference, no slot leaks."""
    reps, engines, roles = mk_roled_fleet(n_prefill=2, n_decode=2)
    srv = FleetServer(reps, engines, max_queue_depth=8)
    # prompt 40 => ~2.5s of modeled prefill at chunk 16: t=1.0 is mid-prefill
    reqs = mk_requests(8, prompt_len=40, max_new=10)
    timeline = (
        TimelineEvent(1.0, "kill", "pf0"),
        TimelineEvent(6.0, "kill", "dc0"),
    )
    rep = srv.serve_stream(reqs, [0.0] * 8, roles=roles, timeline=timeline)

    assert rep.n_served == 8 and rep.n_shed == 0
    assert rep.n_handoffs == 8           # one handoff per request, ever
    for r in reqs:
        assert r.out_tokens == expected_tokens(r), r.rid
    # the real prefill is atomic at completion: a mid-prefill kill loses
    # modeled progress only, the dead engine never fed a prompt
    assert engines["pf0"].prompt_fed == 0
    # dc0's in-flight decodes re-inserted their retained handoffs on dc1
    total_inserts = engines["dc0"].handoffs_in + engines["dc1"].handoffs_in
    assert total_inserts >= 8
    for name, eng in engines.items():
        assert eng.active == 0, (name, eng.active)


def _tiny_disagg_stream(timeline=()):
    """Eight requests (one finishing at insert, max_new 1) served as one
    disaggregated stream of real tiny engines through the fleet server's
    stream call, which hands back the executor.  Returns (executor,
    requests, bytes a handoff holds per cache position)."""
    model, params = tiny_model()
    reps = [Replica("pf0", 2.0), Replica("dc0", 1.0), Replica("dc1", 1.0)]
    engines = {r.name: DecodeEngine(model, params, max_batch=2, max_seq=64,
                                    name=r.name) for r in reps}
    roles = {"pf0": "prefill", "dc0": "decode", "dc1": "decode"}
    srv = FleetServer(reps, engines, max_queue_depth=4)
    reqs = [Request(rid=i, prompt=[(7 * i + j) % 64 for j in range(3 + 2 * i)],
                    max_new_tokens=1 + i % 4) for i in range(8)]
    _, ex = srv._stream(reqs, [0.0] * 8, roles=roles, timeline=timeline)
    per_pos = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        jax.eval_shape(lambda: model.init_cache(1, 1))))
    return ex, reqs, per_pos


def test_finished_requests_release_their_handoffs():
    """No handoff cache outlives its request: after the stream the executor
    holds none and its held-bytes counter is back to 0, while its peak is
    the most that unfinished requests held at once, under the sum of all."""
    ex, reqs, per_pos = _tiny_disagg_stream()
    assert all(r.done and len(r.out_tokens) == r.max_new_tokens for r in reqs)
    assert ex.n_handoffs == 8 and ex.handoffs == {}
    assert ex.handoff_bytes_held == 0
    sizes = [length_bucket(len(r.prompt), 64) * per_pos for r in reqs]
    assert max(sizes) <= ex.handoff_bytes_peak < sum(sizes)


def test_decode_kill_reinserts_held_handoffs_then_releases_them():
    """A decode replica killed mid-stream: its heir re-inserts the handoffs
    of the unfinished requests, which were still held; every request ends
    exactly once with the undisturbed stream's tokens, and nothing is held
    afterwards."""
    _, want, _ = _tiny_disagg_stream()
    ex, reqs, _ = _tiny_disagg_stream(
        timeline=(TimelineEvent(2.0, "kill", "dc0"),))
    assert [r.out_tokens for r in reqs] == [r.out_tokens for r in want]
    assert ex.n_handoffs == 8 and ex.handoffs == {}
    assert ex.handoff_bytes_held == 0 and ex.handoff_bytes_peak > 0


def test_cluster_disagg_reports_handoff_bytes():
    model, params = tiny_model()
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3, 4][: 2 + i % 3],
                    max_new_tokens=2 + i % 3) for i in range(6)]
    rep = Cluster("pf0=2.0^prefill,dc0=1.0x2^decode").serve(
        ServeJob(reqs, model=model, params=params, max_seq=64))
    m = rep.metrics
    assert m["n_handoffs"] == 6
    assert m["handoff_bytes_peak"] > 0


# ================================================================= cluster
ROLED = "pf0=2.0^prefill,dc0=1.0x4^decode,dc1=1.0x4^decode"


def test_cluster_disagg_implicit_burst_report():
    """A roled fleet with no workload clauses serves the pool as a t=0
    burst through the open-loop disagg plane and reports the full split."""
    reqs = mk_requests(8, prompt_len=20, max_new=6)
    rep = Cluster(ROLED).serve(ServeJob(reqs, engine_factory=stub_factory))
    m = rep.metrics
    assert m["mode"] == "disaggregated"
    assert m["n_served"] == 8 and m["n_handoffs"] == 8
    assert m["ttft_split"]["n"] == 8
    assert set(m["role_quality"]) == {"prefill", "decode"}
    assert m["roles"] == {"prefill": ["pf0"], "decode": ["dc0", "dc1"]}
    assert sum(m["role_shares"]["decode"].values()) == 8
    for r in reqs:
        assert r.out_tokens == expected_tokens(r)


def test_cluster_disagg_poisson_with_decode_kill():
    reqs = mk_requests(40, prompt_len=16, max_new=6)
    rep = Cluster(ROLED).serve(
        ServeJob(reqs, engine_factory=stub_factory),
        scenario="arrive:poisson(4)@0-8;kill:dc0@3")
    m = rep.metrics
    assert m["mode"] == "disaggregated"
    assert m["n_served"] > 0
    assert m["n_handoffs"] >= m["n_served"]
    for r in rep.artifact:
        if r.out_tokens:
            assert r.out_tokens == expected_tokens(r), r.rid


def test_cluster_mixed_fleet_report_has_no_disagg_fields():
    """Migration guarantee: a role-free fleet never enters the disagg plane
    or grows disagg report fields."""
    rep = Cluster("a=2x2,b=1x2").serve(
        ServeJob(mk_requests(6), engine_factory=stub_factory))
    assert rep.metrics.get("mode", "waves") != "disaggregated"
    for key in ("ttft_split", "role_quality", "role_shares", "n_handoffs"):
        assert key not in rep.metrics
