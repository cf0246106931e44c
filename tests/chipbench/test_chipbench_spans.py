"""The readers of the program's spans: on spans with values worked by hand,
on the spans of a tiny traced serve, and their None where a run has no
spans to read."""

import json
import os
import types

import jax
import pytest

from chipbench import xtrace
from chipbench.bench import HERE, Bench
from chipbench.harness import Ctx
from repro.cluster import Cluster, ServeJob
from repro.configs.registry import get_config
from repro.models.model import Model
from repro.obs import Span, Tracer
from repro.serve.engine import Request

MS = 1_000_000                    # nanoseconds
READERS = ("queue_wait_p90_ms", "handoff_wait_p90_ms", "slot_occupancy",
           "decode_sample_ms", "sample_idle_share")
FIXTURE = os.path.join(HERE, "testdata", "trace_v5e_qwen2_disagg.json")


def read(name, ctx):
    return Bench(os.path.dirname(HERE)).reader(name)(ctx)


def span(name, t0_ms, t1_ms, rid=None, parent=None, id=0, **attrs):
    return Span(name, id, parent, "d", rid, int(t0_ms * MS),
                None if t1_ms is None else int(t1_ms * MS), attrs,
                keyed=name.startswith("request."))


def window_trace(ops=(), spans=()):
    return xtrace.Trace({"host": [["chipbench.window", 0, 1000]], "modules": [],
                         "ops": [["fusion", "fusion", s, e - s] for s, e in ops],
                         "spans": [list(s) for s in spans]})


def test_queue_wait_is_backlog_plus_queue_per_request():
    spans = []
    for r in range(1, 11):        # request r waits 0.4 r + 0.6 r = r ms
        spans += [span("request.backlog", 0, 0.4 * r, rid=r),
                  span("request.queue", 0.4 * r, r, rid=r)]
    spans += [span("request.backlog", 0, 50, rid=11),      # still queued
              span("request.queue", 50, None, rid=11),
              span("engine.prefill", 0, 99, rid=3)]        # not a wait
    ctx = types.SimpleNamespace(spans=spans)
    # p90 of 1..10 ms, linear between order statistics: 9 + 0.1 * 1.
    assert read("queue_wait_p90_ms", ctx) == pytest.approx(9.1)


def test_queue_wait_counts_a_request_with_no_backlog():
    ctx = types.SimpleNamespace(spans=[span("request.queue", 1, 3, rid=0)])
    assert read("queue_wait_p90_ms", ctx) == pytest.approx(2.0)


def test_handoff_wait_p90():
    ctx = types.SimpleNamespace(spans=[
        span("request.handoff", 10, 10 + w, rid=i) for i, w in enumerate((2, 4, 6, 8))])
    # p90 of 2, 4, 6, 8: position 2.7, so 6 + 0.7 * 2.
    assert read("handoff_wait_p90_ms", ctx) == pytest.approx(7.4)


def test_slot_occupancy_sums_active_over_slots():
    ctx = types.SimpleNamespace(spans=[
        span("engine.step", 0, 1, active=a, max_batch=m)
        for a, m in ((4, 4), (2, 4), (1, 2), (0, 2))])
    assert read("slot_occupancy", ctx) == pytest.approx(100 * 7 / 12)


def test_decode_sample_is_fetch_plus_sample_per_decode_step():
    ctx = types.SimpleNamespace(spans=[
        span("engine.step", 0, 10, id=1, active=2, max_batch=4),
        span("engine.step.fetch", 5, 6, parent=1),
        span("engine.step.sample", 6, 8, parent=1),
        span("engine.step", 10, 20, id=2, active=1, max_batch=4),
        span("engine.step.fetch", 12, 15, parent=2),
        span("engine.step.sample", 15, 15.5, parent=2),
        span("engine.step", 20, 21, id=3, active=0, max_batch=4),
        span("engine.prefill.fetch", 30, 40, parent=9),
    ])
    assert read("decode_sample_ms", ctx) == pytest.approx((3 + 3.5) / 2)


def test_sample_idle_share_is_idle_device_under_host_sampling():
    tr = window_trace(
        ops=((100, 300), (500, 900)),
        spans=(("repro.engine.step", 0, 1000),             # not sampling
               ("repro.engine.step.fetch", 200, 200),      # idle 300-400
               ("repro.engine.step.sample", 400, 200),     # idle 400-500
               ("repro.engine.step.fetch", 950, 150)))     # clipped: 950-1000
    assert read("sample_idle_share", types.SimpleNamespace(trace=tr)) == pytest.approx(25.0)


def test_readers_find_nothing_without_spans():
    with open(FIXTURE) as f:
        tr = xtrace.Trace(json.load(f))
    # As the harness builds its context for a cluster with no tracer.
    ctx = Ctx(trace=tr, calls=[], window_s=tr.window_s, dims=None, program=None, peak={})
    assert [read(n, ctx) for n in READERS] == [None] * len(READERS)


def test_readers_find_nothing_in_spans_that_lack_their_names():
    tr = window_trace(ops=((0, 500),), spans=(("repro.runtime.tick", 0, 900),))
    ctx = types.SimpleNamespace(trace=tr, spans=[span("runtime.tick", 0, 1),
                                                 span("serve.wave", 0, 2)])
    assert [read(n, ctx) for n in READERS] == [None] * len(READERS)
    no_device = window_trace(spans=(("repro.engine.step.fetch", 0, 900),))
    assert read("sample_idle_share", types.SimpleNamespace(trace=no_device)) is None


@pytest.mark.parametrize("fleet", ["a=1x4,b=1x2", "p=1^prefill,d=1x4^decode"])
def test_readers_on_the_spans_of_a_tiny_serve(fleet):
    """What a traced ``Cluster`` records is what the readers read."""
    cfg = get_config("qwen2-1.5b", reduced=True)
    model = Model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3, 4][: 2 + i % 3], max_new_tokens=2 + i % 3)
            for i in range(9)]
    tracer = Tracer()
    Cluster(fleet, backend="wallclock", trace=tracer).serve(
        ServeJob(reqs, model=model, params=params, max_seq=32, max_queue_depth=2))
    ctx = types.SimpleNamespace(spans=tracer.spans)
    steps = [s for s in tracer.spans if s.name == "engine.step"]
    assert 0 < read("slot_occupancy", ctx) <= 100
    assert 0 < read("decode_sample_ms", ctx) < 1e3 * max(s.seconds for s in steps)
    assert read("queue_wait_p90_ms", ctx) > 0
    handoff = read("handoff_wait_p90_ms", ctx)
    assert (handoff is not None) == fleet.startswith("p=")
