"""The engine the benchmark hands ``Cluster.serve``: host-clock stamps.

``StampedEngine`` is the program's ``DecodeEngine`` with a stamp on the host
clock as ``step``, ``prefill`` and ``insert`` return.  ``step`` and
``prefill`` already wait for the device (they pull the logits to the host),
so no synchronisation is added.  Each stamp records which request got which
token, and each call's span and the slot positions it served, for the
per-layer readers.  With a trace running, each call is also a
``TraceAnnotation`` on the profiler's host timeline.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax

from repro.serve.engine import DecodeEngine


@dataclasses.dataclass
class Call:
    kind: str                 # "step", "prefill" or "insert"
    engine: str
    t0: float
    t1: float
    positions: tuple = ()     # step: cache position of each active slot
    length: int = 0           # prefill: prompt length; insert: its position
    bucket: int = 0           # prefill: the padded length


@dataclasses.dataclass
class Log:
    """Everything the engines stamped, on ``time.perf_counter``."""

    token_t: dict = dataclasses.field(default_factory=dict)    # rid -> [t]
    token_id: dict = dataclasses.field(default_factory=dict)   # rid -> [id]
    finished: dict = dataclasses.field(default_factory=dict)   # rid -> count
    calls: list = dataclasses.field(default_factory=list)
    annotate: bool = False
    after_call: object = None    # called after every engine call

    def clear(self) -> None:
        self.token_t.clear(), self.token_id.clear(), self.finished.clear()
        self.calls.clear()

    def call(self, c: Call) -> None:
        self.calls.append(c)
        if self.after_call is not None:
            self.after_call()

    def token(self, rid: int, tok: int, t: float) -> None:
        self.token_t.setdefault(rid, []).append(t)
        self.token_id.setdefault(rid, []).append(tok)


def span(log: Log, kind: str):
    """A ``chipbench.<kind>`` annotation on the profiler's host timeline
    while a trace runs; nothing otherwise."""
    if log.annotate:
        return jax.profiler.TraceAnnotation(f"chipbench.{kind}")
    return contextlib.nullcontext()


class StampedEngine(DecodeEngine):
    def __init__(self, *args, log: Log, **kwargs):
        super().__init__(*args, **kwargs)
        self.log = log

    def step(self):
        self._admit()
        live = [(s.req, len(s.req.out_tokens)) for s in self.slots if s.req is not None]
        positions = tuple(s.pos for s in self.slots if s.req is not None)
        with span(self.log, "step"):
            t0 = time.perf_counter()
            done = super().step()
            t1 = time.perf_counter()
        for req, n in live:
            for tok in req.out_tokens[n:]:
                self.log.token(req.rid, tok, t1)
        for r in done:
            self.log.finished[r.rid] = self.log.finished.get(r.rid, 0) + 1
        if positions:
            self.log.call(Call("step", self.name, t0, t1, positions))
        return done

    def prefill(self, req):
        with span(self.log, "prefill"):
            t0 = time.perf_counter()
            h = super().prefill(req)
            t1 = time.perf_counter()
        self.log.token(req.rid, h.first_token, t1)
        self.log.call(Call("prefill", self.name, t0, t1, length=len(req.prompt),
                           bucket=h.bucket))
        return h

    def insert(self, handoff):
        with span(self.log, "insert"):
            t0 = time.perf_counter()
            idx = super().insert(handoff)
            t1 = time.perf_counter()
        if idx < 0:
            rid = handoff.req.rid
            self.log.finished[rid] = self.log.finished.get(rid, 0) + 1
        self.log.call(Call("insert", self.name, t0, t1, length=handoff.pos,
                           bucket=handoff.bucket))
        return idx

