"""The trace reduction, against a short stretch recorded on a TPU v5e
(Qwen2-1.5B, disaggregated fleet: one prefill of a 1024-token bucket and the
decode steps around it)."""

import json
import os

import numpy as np
import pytest

from chipbench import xtrace
from chipbench.bench import HERE

FIXTURE = os.path.join(HERE, "testdata", "trace_v5e_qwen2_disagg.json")


@pytest.fixture(scope="module")
def events():
    with open(FIXTURE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def trace(events):
    return xtrace.Trace(events)


def test_window_comes_from_the_annotation(trace, events):
    (win,) = [h for h in events["host"] if h[0] == "chipbench.window"]
    assert trace.window_s == pytest.approx(win[2] * 1e-9)


def test_busy_is_the_union_of_op_intervals(trace, events):
    # Independent count: a raster of the window at 1 microsecond.
    t0 = trace.t0
    n = int(np.ceil((trace.t1 - t0) / 1e3))
    busy = np.zeros(n, bool)
    for _, _, s, d in events["ops"]:
        a, b = max(s, t0), min(s + d, trace.t1)
        if b > a:
            busy[int((a - t0) // 1e3):int(np.ceil((b - t0) / 1e3))] = True
    assert 0 < trace.busy_s <= trace.window_s
    assert trace.busy_s == pytest.approx(busy.sum() * 1e-6, abs=2e-6 * len(trace.busy) + 1e-5)


def test_idle_gaps_add_up_to_the_idle_time(trace):
    gaps = trace.idle_gaps()
    assert sum(v for _, v in gaps) == pytest.approx(trace.window_s - trace.busy_s, rel=1e-9)
    assert {n for n, _ in gaps} <= {"runtime", "engine.step", "engine.prefill", "engine.insert"}


def test_calls_count_only_inside_the_stretch(trace, events):
    prefills = [h for h in events["host"] if h[0] == "chipbench.prefill"]
    assert len(prefills) == 2          # the second ends after the stretch
    assert trace.count("prefill") == 1
    assert trace.count("step") == 0    # the only step began before it


def test_module_seconds_are_the_programs_inside_the_call(trace, events):
    (s0, d0) = [(s, d) for n, s, d in events["host"]
                if n == "chipbench.prefill" and s + d <= trace.t1][0]
    want = sum(d for _, s, d in events["modules"] if s0 <= s <= s0 + d0)
    assert want > 0
    assert trace.module_seconds("prefill") == pytest.approx(want * 1e-9)


def test_kernel_ops_one_per_layer(trace):
    seconds, n = trace.op_seconds("prefill_flash")
    assert n == 2 * 28                 # two prefills of 28 layers finish inside
    assert 0 < seconds < trace.window_s


def test_top_ops_leave_out_loops(trace):
    top = trace.top_ops()
    assert len(top) == 10
    assert top[0][0] == "jit_run/prefill_flash.6"
    assert all("/while" not in name for name, _ in top)
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)


@pytest.mark.parametrize("text,want", [
    ("%prefill_flash.6 = bf16[16,1024,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call(bf16[16]"
     " %a), custom_call_target=\"tpu_custom_call\"", ("prefill_flash.6", "custom-call")),
    ("%while.3 = (s32[]{:T(128)}, bf16[1,4096,1536]{1,2,0:T(8,128)(2,1)S(1)}) while((s32[])"
     " %tuple.48), condition=%c", ("while.3", "while")),
    ("%copy.101 = bf16[28,16,4096,2,128]{4,3,2,1,0:T(2,128)(2,1)} copy(bf16[28] %g)",
     ("copy.101", "copy")),
])
def test_op_name(text, want):
    assert xtrace.op_name(text) == want
