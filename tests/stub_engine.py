"""Model-free stub DecodeEngine for timing-scale serving tests.

Reproduces DecodeEngine's slot/step/heartbeat/cancel bookkeeping with a
deterministic token function instead of a forward pass, so fleet-serving
invariants (proportional allotment, mid-bundle quality, exactly-once decode)
run in milliseconds in tier-1.  Shared by the serving, fleet, stream,
disaggregation and cluster tests.
"""

import dataclasses

import numpy as np

from repro.serve import FleetServer, KVHandoff, Replica, Request


def stub_token(rid: int, k: int) -> int:
    """Deterministic 'decode': token k of request rid."""
    return (rid * 31 + k * 7) % 97


def _stub_bucket(n: int, max_seq: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return min(b, max_seq)


@dataclasses.dataclass
class _Slot:
    req: Request | None = None
    pos: int = 0
    fed: int = 0


class StubEngine:
    """DecodeEngine's continuous-batching bookkeeping without the model:
    same submit/step/cancel/heartbeat surface, token k of request rid is
    ``stub_token(rid, k)``."""

    def __init__(self, max_batch=4, max_seq=128, name="stub"):
        self.name = name
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.slots = [_Slot() for _ in range(max_batch)]
        self.queue: list[Request] = []
        self.steps = 0
        self.tokens_out = 0
        self.prompt_fed = 0
        self.handoffs_in = 0
        self._hb_steps = 0
        self._hb_tokens = 0
        self._hb_fed = 0

    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new_tokens > self.max_seq:
            raise ValueError("request exceeds engine max_seq")
        req.submit_step = self.steps
        self.queue.append(req)

    def _admit(self) -> None:
        for slot in self.slots:
            if slot.req is None and self.queue:
                slot.req = self.queue.pop(0)
                slot.pos = 0
                slot.fed = 0

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s.req is not None)

    def step(self) -> list[Request]:
        self._admit()
        if self.active == 0:
            return []
        self.steps += 1
        finished = []
        for slot in self.slots:
            r = slot.req
            if r is None:
                continue
            slot.pos += 1
            if slot.fed < len(r.prompt):
                slot.fed += 1
                self.prompt_fed += 1
                if slot.fed < len(r.prompt):
                    continue
            r.out_tokens.append(stub_token(r.rid, len(r.out_tokens)))
            self.tokens_out += 1
            if len(r.out_tokens) >= r.max_new_tokens or slot.pos >= self.max_seq:
                r.done = True
                r.finish_step = self.steps
                finished.append(r)
                slot.req = None
        return finished

    def run_until_drained(self, max_steps=10_000) -> list[Request]:
        done = []
        for _ in range(max_steps):
            done.extend(self.step())
            if self.active == 0 and not self.queue:
                break
        return done

    def cancel(self, rid: int) -> Request | None:
        for i, r in enumerate(self.queue):
            if r.rid == rid:
                return self.queue.pop(i)
        for slot in self.slots:
            r = slot.req
            if r is not None and r.rid == rid:
                slot.req = None
                slot.pos = 0
                slot.fed = 0
                r.out_tokens = []
                r.done = False
                r.finish_step = 0
                return r
        return None

    def prefill(self, req: Request) -> KVHandoff:
        """Stub bucketed prefill: whole prompt in 'one call', first token is
        ``stub_token(rid, 0)`` — same as the teacher-forced first sample."""
        L = len(req.prompt)
        if L == 0:
            raise ValueError("prefill needs a non-empty prompt")
        if L + req.max_new_tokens > self.max_seq:
            raise ValueError("request exceeds engine max_seq")
        self.prompt_fed += L
        self.tokens_out += 1
        return KVHandoff(
            req=req, pos=L, first_token=stub_token(req.rid, 0),
            caches={"stub": np.asarray(req.rid)}, source=self.name,
            bucket=_stub_bucket(L, self.max_seq),
        )

    def insert(self, handoff: KVHandoff) -> int:
        r = handoff.req
        if len(r.prompt) + r.max_new_tokens > self.max_seq:
            raise ValueError("request exceeds engine max_seq")
        r.submit_step = self.steps
        r.out_tokens = [handoff.first_token]
        r.done = False
        self.handoffs_in += 1
        if r.max_new_tokens <= 1:
            r.done = True
            r.finish_step = self.steps
            return -1
        idx = next(
            (i for i, s in enumerate(self.slots) if s.req is None), None
        )
        if idx is None:
            raise RuntimeError(
                f"engine {self.name!r}: no free slot for handoff insert"
            )
        slot = self.slots[idx]
        slot.req = r
        slot.pos = handoff.pos
        slot.fed = len(r.prompt)
        return idx

    def heartbeat(self, now_s, seconds_per_step=1.0):
        from repro.core import PerfReport

        steps = self.steps - self._hb_steps
        work = (self.tokens_out - self._hb_tokens) + (
            self.prompt_fed - self._hb_fed
        )
        if steps <= 0 or work <= 0:
            return None
        self._hb_steps, self._hb_tokens = self.steps, self.tokens_out
        self._hb_fed = self.prompt_fed
        return PerfReport(self.name, float(work), steps * seconds_per_step,
                          now_s)


def mk_requests(n, prompt_len=2, max_new=6):
    return [
        Request(rid=i, prompt=[(i + j) % 50 for j in range(prompt_len)],
                max_new_tokens=max_new)
        for i in range(n)
    ]


def expected_tokens(r: Request) -> list[int]:
    return [stub_token(r.rid, k) for k in range(r.max_new_tokens)]


def stub_fleet(specs, **kw):
    """A FleetServer over stub engines.  ``specs``: (name, step clock,
    slots) per replica.  Returns the server and its engines."""
    replicas = [Replica(n, p) for n, p, _ in specs]
    engines = {n: StubEngine(max_batch=b, name=n) for n, _, b in specs}
    return FleetServer(replicas, engines, **kw), engines
