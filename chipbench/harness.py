"""One run of one cell: set-up, the measured window, the check, the result.

Set-up builds the model's weights on the device from the seed, one
``Cluster`` over the cell's fleet on the wall-clock backend, and serves one
warm-up pool through it, which compiles every program the window will run.
The window then hands ``Cluster.serve`` pools of the cell's traffic, back to
back, until ``seconds`` have passed since it opened; the window closes when
the last pool returns.  Every time is the host's clock, stamped by the
engines (``stamps.py``).  After the window the program's state is freed and
a sample of the served requests is checked against the float32 reference.
"""

from __future__ import annotations

import dataclasses
import gc
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import numpy as np

from repro.cluster import Cluster, FleetSpec, ServeJob
from repro.kernels.prefill.ops import length_bucket
from repro.models.model import Model

from . import reference, stats, traffic, weights as wts, xtrace
from .bench import Bench, Cell
from .model import dims, program_config
from .stamps import Log, StampedEngine

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# Length of the traced stretch of a --trace 1 run, from the window's opening.
TRACE_SECONDS = 6.0


def note(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def slots_of(fleet: FleetSpec) -> int:
    """Slots that decode: every replica's but a prefill replica's."""
    return sum(w.concurrency for w in fleet.workers if w.role != "prefill")


def warmup_pool(cell: Cell, fleet: FleetSpec, vocab: int, seed: int) -> list:
    """Short requests that run every program the cell's traffic reaches:
    with a prefill pool, one prompt per prefill bucket of the mix's prompt
    range; without one, a request per slot so every replica decodes."""
    mix, out = cell.traffic, cell.traffic["output"]["min"]
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    if any(w.role == "prefill" for w in fleet.workers):
        lengths, b = [], length_bucket(lo, cell.max_seq)
        while True:
            lengths.append(max(lo, min(b, hi, cell.max_seq - out)))
            if b >= length_bucket(hi, cell.max_seq):
                break
            b *= 2
    else:
        lengths = [lo] * slots_of(fleet)
    rng = np.random.default_rng([int(seed), 1 << 20])
    return [traffic.Request(rid=-1 - i, prompt=rng.integers(0, vocab, n).tolist(),
                            max_new_tokens=out)
            for i, n in enumerate(lengths)]


def check_sample(pools: list, seed: int, want_tokens: int) -> list:
    """Requests to check against the reference, drawn from the seed: the
    longest one, then others until ``want_tokens`` served tokens."""
    reqs = [r for _, _, rs in pools for r in rs]
    longest = max(range(len(reqs)),
                  key=lambda i: len(reqs[i].prompt) + reqs[i].max_new_tokens)
    order = [longest] + [int(i) for i in
                         np.random.default_rng([int(seed), 2]).permutation(len(reqs))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= want_tokens:
            break
        out.append(reqs[i])
        n += reqs[i].max_new_tokens
    return out


def delivery_failures(pools: list, log: Log) -> list:
    """Requests not served exactly once with all of their tokens, as
    stamped where they were produced."""
    bad = []
    for _, _, reqs in pools:
        for r in reqs:
            got = log.token_id.get(r.rid, [])
            if (log.finished.get(r.rid, 0) != 1 or len(got) != r.max_new_tokens
                    or list(r.out_tokens) != got):
                bad.append(r.rid)
    return bad


@dataclasses.dataclass
class Ctx:
    """What a per-layer reader reads: the trace of the traced stretch and
    the engine calls that fell inside it."""

    trace: xtrace.Trace
    calls: list            # stamps.Call inside the traced stretch
    window_s: float        # host-clock length of the traced stretch
    dims: object           # model.Dims
    program: object        # the program's ModelConfig
    peak: dict


class Stretch:
    """The traced stretch: a profiler trace from the window's opening for
    ``seconds``, or to the window's close if that comes first.  ``poll`` runs
    after every engine call and ends the trace once its time is up."""

    def __init__(self, seconds: float):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.seconds = seconds
        self.active = False

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(f"{xtrace.PREFIX}window")
        self.span.__enter__()
        self.t0 = time.perf_counter()
        self.active = True

    def poll(self) -> None:
        if self.active and time.perf_counter() - self.t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        self.t1 = time.perf_counter()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def read(self) -> xtrace.Trace:
        path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)[0]
        try:
            return xtrace.Trace(xtrace.load(path))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Session:
    """A cell set up for a seed: weights, the program's engines behind one
    ``Cluster``, and the log their stamps go to."""

    cell: Cell
    dims: object
    model: Model
    weights: dict
    serve: object          # serve(pool): one Cluster.serve call
    log: Log
    slots: int


def setup(bench: Bench, name: str, seed: int, annotate: bool = False) -> Session:
    """Weights from the seed, the fleet, and one warm-up pool served."""
    cell = bench.cell(name)
    d = dims(cell.config)
    model = Model(program_config(cell.config))
    fleet = FleetSpec.parse(cell.fleet)
    t = time.perf_counter()
    weights = wts.make_weights(d, seed)
    params = wts.pack(weights, d, model.abstract_params())
    note(f"weights made in {time.perf_counter() - t:.3f} s")
    log = Log(annotate=annotate)

    def factory(spec):
        return StampedEngine(model, params, max_batch=spec.concurrency,
                             max_seq=cell.max_seq, name=spec.name, log=log)

    cluster = Cluster(fleet, backend="wallclock")

    def serve(reqs):
        cluster.serve(ServeJob(reqs, engine_factory=factory, max_seq=cell.max_seq,
                               max_queue_depth=cell.max_queue_depth))

    t = time.perf_counter()
    serve(warmup_pool(cell, fleet, d.vocab_size, seed))
    note(f"warm-up pool served in {time.perf_counter() - t:.3f} s")
    log.clear()
    return Session(cell, d, model, weights, serve, log, slots_of(fleet))


def window(s: Session, seed: int, seconds: float, stretch: Stretch | None = None):
    """Pools back to back until ``seconds`` have passed since the window
    opened.  Returns the pools ``(submitted, returned, requests)``, the
    window's open and close, and the programs lowered inside it."""
    lowerings = []

    def listener(event, secs, **kw):
        if event == LOWERING_EVENT:
            lowerings.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    if stretch is not None:
        s.log.after_call = stretch.poll
        stretch.start()
    pools, rid = [], 0
    t_open = time.perf_counter()
    try:
        while not pools or time.perf_counter() - t_open < seconds:
            reqs = traffic.pool(s.cell.traffic, s.slots, s.dims.vocab_size, seed,
                                len(pools), rid)
            t_sub = time.perf_counter()
            s.serve(reqs)
            pools.append((t_sub, time.perf_counter(), reqs))
            rid += len(reqs)
    finally:
        if stretch is not None:
            stretch.stop()
        jax.monitoring.unregister_event_duration_listener(listener)
    t_close = pools[-1][1]
    note(f"window: {len(pools)} pools in {t_close - t_open:.3f} s; "
         f"{len(lowerings)} programs lowered inside it")
    return pools, t_open, t_close


def release(s: Session) -> None:
    """Free the program's state (engines, caches, packed weights); the
    benchmark's weights stay for the reference."""
    s.serve = None
    gc.collect()


def max_gap(s: Session, sample: list, gaps=reference.served_gaps) -> float:
    """The widest gap over the served tokens of ``sample``."""
    out = 0.0
    for r in sample:
        served = s.log.token_id.get(r.rid, [])
        if served:
            out = max(out, float(np.max(gaps(s.weights, s.dims, r.prompt, served))))
    return out


def run_cell(bench: Bench, name: str, seed: int, seconds: float, trace: bool,
             peak: dict, t_start: float, device: dict) -> dict:
    s = setup(bench, name, seed, annotate=trace)
    stretch = Stretch(TRACE_SECONDS) if trace else None
    pools, t_open, t_close = window(s, seed, seconds, stretch)
    mem = jax.devices()[0].memory_stats() or {}
    device = {**device, "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    failed = delivery_failures(pools, s.log)
    release(s)

    t = time.perf_counter()
    sample = check_sample(pools, seed, s.cell.check["sample_tokens"])
    gap = max_gap(s, sample)
    note(f"reference check of {len(sample)} requests in {time.perf_counter() - t:.3f} s")
    checks = {
        "max_logit_gap": {"value": gap, "limit": s.cell.check["max_logit_gap"]},
        "undelivered": {"value": len(failed), "limit": 0},
    }
    correct = bool(np.isfinite(gap)) and all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct,
              "attempted": sum(len(rs) for _, _, rs in pools),
              "failed": len(failed)}
    if trace:
        tr = stretch.read()
        ctx = Ctx(trace=tr, dims=s.dims, program=s.model.cfg, peak=peak,
                  calls=[c for c in s.log.calls if stretch.t0 <= c.t0 and c.t1 <= stretch.t1],
                  window_s=stretch.t1 - stretch.t0)
        metrics = {}
        for m in s.cell.per_layer:
            v = bench.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        result.update(metrics=metrics, device=device,
                      breakdown={"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()})
    else:
        values = stats.window_metrics(pools, s.log, t_open, t_close)
        values["setup_s"] = t_open - t_start
        result.update(metrics={m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                               for m in s.cell.end_to_end},
                      device=device)
    result["checks"] = checks
    return result
