"""Fleet-serving benchmark on the batched EngineExecutor path.

Real-model scale (a small but fully compiled decoder, real ``DecodeEngine``
replicas; ``tests/test_fleet.py`` asserts the same invariants at timing
scale with stub engines), driven through the declarative Cluster API.  Two
measurements on the same request distribution:

  batched   engines as incremental runtime executors: slots stay full,
            durations are measured engine-step counts on each replica's step
            clock, heartbeats are measured tokens/sec,
  fault     the batched path with the first replica's step clock *halved
            mid-bundle* (``halve:r0@25%``) after a warm wave — the
            homogenization-quality number under mid-bundle degradation.

A third measurement exercises the open-loop stack end to end:

  sustained  requests *arrive* (Poisson + a burst) instead of being planned
             as waves; full queues shed; the first replica's clock halves
             mid-stream and a ``scale:`` rule joins a replica from a
             measured p99-TTFT breach.  Reports tokens/sec, p50/p99 TTFT,
             shed rate, goodput under deadline, and the autoscaled
             replica's share of the work.

A fourth compares serving planes on identical hardware and arrivals:

  disagg     the same sustained Poisson stream served twice — once by a
             mixed-role fleet (prompts teacher-forced through the decode
             step, one token per tick) and once by the same fleet split
             into a prefill pool (bucketed one-call prefill) and a decode
             pool (KV handoff insert).  Reports both p99 TTFTs, the TTFT
             split, and handoff counts, with backend provenance.

Acceptance: fault quality <= 1.3.  The sustained entry has non-null
p50/p99 TTFT, a nonzero shed rate under the Poisson overload, the
autoscaled join visible in the shares, and survivor quality <= 1.3 under
the mid-stream halve.  The disagg entry beats the mixed baseline on p99
TTFT (``p99_ttft_speedup > 1``).  The fleet spec and scenario DSL strings
ride into the JSON for traceability.  Output: ``BENCH_serve.json``.

Run:   PYTHONPATH=src python -m benchmarks.bench_serve
Toy:   PYTHONPATH=src python -m benchmarks.bench_serve --requests 12 --max-new 4
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro.cluster import Cluster, FleetSpec, Scenario, ServeJob
from repro.launch.serve import make_requests
from repro.models import LayerSpec, Model, ModelConfig


def bench_model() -> Model:
    return Model(ModelConfig(
        name="bench-serve", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
        d_ff=128, vocab_size=128, head_dim=16,
        layer_pattern=(LayerSpec("attn", "dense"),),
        param_dtype="float32", compute_dtype="float32", use_pallas=False,
        rope_theta=1e4,
    ))


def summarize(rep, wall_s: float) -> dict:
    return {
        "n_requests": rep.metrics["n_requests"],
        "tokens_out": int(rep.work_done),
        "sim_time_s": rep.sim_time_s,
        "tokens_per_s": rep.throughput,
        "worst_quality": rep.homogenization_quality(),
        "n_waves": rep.n_phases,
        "wall_s": wall_s,
    }


def run_bench(n_requests: int, max_new: int, fleet: FleetSpec | str,
              max_seq: int, queue_depth: int, seed: int = 0) -> dict:
    fleet = FleetSpec.parse(fleet, prefix="r")
    model = bench_model()
    params = model.init(jax.random.key(0))
    vocab = model.cfg.vocab_size
    scenario = Scenario.parse(f"halve:{fleet.names[0]}@25%")

    def job(reqs, **kw):
        kw.setdefault("max_queue_depth", queue_depth)
        return ServeJob(reqs, model=model, params=params, max_seq=max_seq, **kw)

    out = {"config": {
        "n_requests": n_requests, "max_new": max_new,
        "fleet": str(fleet),
        "replicas": [{"name": w.name, "perf": w.perf, "max_batch": w.concurrency}
                     for w in fleet.workers],
        "max_seq": max_seq, "queue_depth": queue_depth,
    }, "scenario": str(scenario)}

    reqs = make_requests(n_requests, vocab, max_new, seed=seed)
    t0 = time.perf_counter()
    rep = Cluster(fleet).serve(job(reqs))
    out["batched"] = summarize(rep, time.perf_counter() - t0)

    # Mid-bundle perf-halving: warm wave teaches the tracker the true rates,
    # then r0's step clock halves 25% into the measured wave.
    cluster = Cluster(fleet)
    cluster.serve(job(make_requests(n_requests, vocab, max_new, seed=seed + 1)))
    reqs = make_requests(n_requests, vocab, max_new, seed=seed)
    t0 = time.perf_counter()
    rep = cluster.serve(job(reqs), scenario=scenario)
    out["fault"] = summarize(rep, time.perf_counter() - t0)
    out["fault"]["n_migrated"] = rep.n_migrated
    out["fault"]["scenario"] = str(scenario)

    # Sustained load: open-loop arrivals, shed-on-overflow, a mid-stream
    # halve, and a reactive scale-up from a measured p99-TTFT breach.  The
    # pool is oversized — the arrival process decides how many requests the
    # stream actually has.
    stream_sc = Scenario.parse(
        f"arrive:poisson(6)@0-10 burst:24@5 halve:{fleet.names[0]}@30% "
        "scale:+1@p99>1.0/12"
    )
    pool = make_requests(max(4 * n_requests, 160), vocab, max_new, seed=seed)
    t0 = time.perf_counter()
    rep = Cluster(fleet, priors="spec").serve(
        job(pool, max_queue_depth=4, overflow="shed", deadline_s=4.0),
        scenario=stream_sc,
    )
    lat = rep.latency
    out["sustained"] = {
        "scenario": str(stream_sc),
        "n_requests": rep.metrics["n_requests"],
        "n_served": rep.metrics["n_served"],
        "n_shed": rep.metrics["n_shed"],
        "shed_rate": lat.shed_rate,
        "tokens_out": int(rep.work_done),
        "tokens_per_s": rep.throughput,
        "p50_ttft_s": lat.p50_ttft_s,
        "p99_ttft_s": lat.p99_ttft_s,
        "p50_token_s": lat.p50_token_s,
        "goodput_rps": lat.goodput_rps,
        "deadline_s": lat.deadline_s,
        "quality": rep.homogenization_quality(),
        "joined": list(rep.metrics["joined"]),
        "joined_shares": {
            w: n for w, n in rep.shares().items()
            if w in rep.metrics["joined"]
        },
        "wall_s": time.perf_counter() - t0,
    }

    # Disaggregation A/B: identical hardware and identical Poisson arrivals,
    # served by the mixed plane vs the prefill/decode-split plane.  Longer
    # prompts than the wave benches — prompt feeding is exactly what the
    # bucketed prefill fast path removes from the TTFT.
    import numpy as np

    from repro.serve.engine import Request

    def long_prompt_pool(n: int, prompt_len: int, seed: int):
        rng = np.random.default_rng(seed)
        return [
            Request(rid=i, prompt=list(rng.integers(0, vocab, prompt_len)),
                    max_new_tokens=max_new)
            for i in range(n)
        ]

    arrive_sc = Scenario.parse("arrive:poisson(0.6)@0-30")
    mixed_ab = FleetSpec.parse("fast=2.0x2,d0=1.0x4,d1=1.0x4")
    disagg_ab = FleetSpec.parse(
        "fast=2.0x2^prefill,d0=1.0x4^decode,d1=1.0x4^decode")

    def ab_run(ab_fleet):
        pool = long_prompt_pool(120, prompt_len=24, seed=seed + 2)
        t0 = time.perf_counter()
        rep = Cluster(ab_fleet, priors="spec").serve(
            job(pool, max_queue_depth=4), scenario=arrive_sc)
        lat = rep.latency
        entry = {
            "fleet": str(ab_fleet),
            "n_served": rep.metrics["n_served"],
            "tokens_per_s": rep.throughput,
            "p50_ttft_s": lat.p50_ttft_s,
            "p99_ttft_s": lat.p99_ttft_s,
            "quality": rep.homogenization_quality(),
            "wall_s": time.perf_counter() - t0,
        }
        if rep.metrics.get("mode") == "disaggregated":
            entry["ttft_split"] = rep.metrics["ttft_split"]
            entry["role_quality"] = rep.metrics["role_quality"]
            entry["n_handoffs"] = rep.metrics["n_handoffs"]
        return rep, entry

    rep_m, mixed_entry = ab_run(mixed_ab)
    rep_d, disagg_entry = ab_run(disagg_ab)
    out["disagg"] = {
        "scenario": str(arrive_sc),
        "prompt_len": 24,
        "backend": rep_d.backend,
        "mixed": mixed_entry,
        "disaggregated": disagg_entry,
        "p99_ttft_speedup": (
            mixed_entry["p99_ttft_s"] / max(disagg_entry["p99_ttft_s"], 1e-12)
        ),
    }
    return out


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--fleet", "--replicas", dest="fleet", default="8x4:4x2:2x1",
                    help="FleetSpec grammar: PERFxSLOTS per replica")
    ap.add_argument("--queue-depth", type=int, default=64,
                    help="large default keeps the fault scenario one wave")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)

    result = run_bench(args.requests, args.max_new, args.fleet, args.max_seq,
                       args.queue_depth)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"batched: {result['batched']['tokens_per_s']:8.2f} tok/s "
          f"(measured engine clocks)")
    print(f"fault  : {result['fault']['tokens_per_s']:8.2f} tok/s with "
          f"[{result['fault']['scenario']}] mid-bundle, quality "
          f"{result['fault']['worst_quality']:.2f}, "
          f"{result['fault']['n_migrated']} requests migrated")
    sus = result["sustained"]
    print(f"sustained: {sus['tokens_per_s']:8.2f} tok/s open-loop, "
          f"p50/p99 TTFT {sus['p50_ttft_s']:.2f}/{sus['p99_ttft_s']:.2f}s, "
          f"shed {sus['n_shed']}/{sus['n_requests']} ({sus['shed_rate']:.1%}), "
          f"quality {sus['quality']:.2f}, "
          f"autoscaled {sus['joined_shares'] or 'none'}")
    dg = result["disagg"]
    print(f"disagg : p99 TTFT {dg['disaggregated']['p99_ttft_s']:.2f}s split "
          f"vs {dg['mixed']['p99_ttft_s']:.2f}s mixed -> "
          f"{dg['p99_ttft_speedup']:.2f}x, "
          f"{dg['disaggregated']['n_handoffs']} handoffs "
          f"[backend={dg['backend']}]")
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()
