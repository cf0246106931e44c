"""The window rule, the percentiles over all samples, and the traffic."""

import json
import os
import types

import numpy as np
import pytest

from chipbench import harness, stats, traffic
from chipbench.bench import HERE
from chipbench.stamps import Log
from repro.serve.engine import Request


def test_percentile_is_over_every_sample():
    assert stats.percentile(list(range(1, 11)), 90) == pytest.approx(9.1)
    vals = np.random.default_rng(0).exponential(size=1001)
    assert stats.percentile(vals, 95) == pytest.approx(float(np.percentile(vals, 95)))
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_window_metrics_count_every_request_and_gap():
    log = Log()
    a, b, c = (Request(i, [1, 2], 3) for i in range(3))
    for rid, ts in ((0, [1.5, 1.6, 1.8]), (1, [2.0, 2.1, 2.2]), (2, [3.0, 3.5, 3.6])):
        for t in ts:
            log.token(rid, 7, t)
    pools = [(1.0, 2.5, [a, b]), (2.5, 4.0, [c])]
    m = stats.window_metrics(pools, log, t_open=1.0, t_close=4.0)
    assert m["output_tokens_per_s"] == pytest.approx(9 / 3.0)
    ttft = [0.5, 1.0, 0.5]             # first token - its pool's submission
    assert m["ttft_p90_ms"] == pytest.approx(1e3 * float(np.percentile(ttft, 90)))
    itl = [0.1, 0.2, 0.1, 0.1, 0.5, 0.1]
    assert m["itl_p95_ms"] == pytest.approx(1e3 * float(np.percentile(itl, 95)))


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now


@pytest.mark.parametrize("seconds,pool_s,want", [(10, 4.0, 3), (10, 12.0, 1), (0, 1.0, 1)])
def test_pools_start_until_the_seconds_are_up(monkeypatch, seconds, pool_s, want):
    clock = FakeClock()
    monkeypatch.setattr(harness, "time", clock)
    monkeypatch.setattr(harness, "note", lambda msg: None)

    def serve(reqs):
        clock.now += pool_s

    with open(os.path.join(HERE, "traffic", "chat.json")) as f:
        mix = json.load(f)
    s = types.SimpleNamespace(cell=types.SimpleNamespace(traffic=mix), slots=2,
                              dims=types.SimpleNamespace(vocab_size=50), serve=serve,
                              log=Log())
    pools, t_open, t_close = harness.window(s, 7, seconds)
    assert len(pools) == want
    assert t_close == pools[-1][1] == t_open + want * pool_s
    assert all(p[0] - t_open < max(seconds, 1e-9) for p in pools)
    rids = [r.rid for _, _, rs in pools for r in rs]
    assert len(rids) == len(set(rids)) == int(2 * mix["pool_per_slot"]) * want


@pytest.mark.parametrize("mix", ["chat", "longprompt"])
def test_every_seed_asks_for_the_same_work(mix):
    with open(os.path.join(HERE, "traffic", f"{mix}.json")) as f:
        m = json.load(f)
    sizes = sorted(traffic.pool_sizes(m, 48))
    assert len(sizes) == int(48 * m["pool_per_slot"])
    assert all(m["prompt"]["min"] <= p <= m["prompt"]["max"] for p, _ in sizes)
    assert all(m["output"]["min"] <= o <= m["output"]["max"] for _, o in sizes)
    pools = [traffic.pool(m, 48, 1000, seed, i, 0)
             for seed, i in ((1, 0), (1, 1), (2**31 + 2**30, 0))]
    for p in pools:
        assert sorted((len(r.prompt), r.max_new_tokens) for r in p) == sizes
    assert [len(r.prompt) for r in pools[0]] != [len(r.prompt) for r in pools[1]]
    assert [len(r.prompt) for r in pools[0]] == [len(r.prompt) for r in pools[2]]
    assert [r.prompt for r in pools[0]] != [r.prompt for r in pools[2]]
    again = traffic.pool(m, 48, 1000, 1, 0, 0)
    assert [r.prompt for r in again] == [r.prompt for r in pools[0]]
