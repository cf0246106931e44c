"""From a profiler trace to the events the per-layer readers use.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of event on the trace's own clock (nanoseconds):

- ``host``: the benchmark's ``chipbench.*`` annotations, ``[name, start,
  duration]``: the traced stretch (``chipbench.window``) and each engine call;
- ``modules``: the device's XLA programs, ``[name, start, duration]``, one
  per execution;
- ``ops``: the device's operations, ``[name, opcode, start, duration]``, with
  the name as the compiled program gives it (``prefill_flash.6``,
  ``fusion.12``).

``Trace`` reduces them: the device's busy time (the union of its operations'
intervals inside the stretch), the device time of the programs run inside
each kind of engine call, the time of named operations, and the idle gaps
labelled with what the host was doing.  The saved form is small JSON, so a
recorded trace can be kept with the tests.
"""

from __future__ import annotations

import bisect
import collections
import json
import re

DEVICE = "/device:TPU:0"
PREFIX = "chipbench."
CALLS = ("step", "prefill", "insert")
# Operations that only hold others (a loop's body runs as operations of
# its own): counted in the busy time, not among the top operations.
CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"^%?(\S+) = .*?\s([a-z][a-z0-9_-]*)\(")


def op_name(text: str) -> tuple[str, str]:
    """An operation's short name and opcode from the trace's HLO text."""
    m = _OP.match(text)
    return (m.group(1), m.group(2)) if m else (text.split(" ")[0].lstrip("%"), "")


def load(path: str) -> dict:
    from jax.profiler import ProfileData

    host, modules, ops = [], [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == DEVICE:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [[e.name, e.start_ns, e.duration_ns] for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [[*op_name(e.name), e.start_ns, e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, e.start_ns, e.duration_ns]
                         for e in line.events if e.name.startswith(PREFIX)]
    return {"host": host, "modules": modules, "ops": ops}


def save(events: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(events, f)


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    def __init__(self, events: dict):
        self.events = events
        win = [h for h in events["host"] if h[0] == PREFIX + "window"]
        if not win:
            raise ValueError("the trace holds no chipbench.window annotation")
        self.t0, self.t1 = win[0][1], win[0][1] + win[0][2]
        self.calls = sorted((s, s + d, n[len(PREFIX):]) for n, s, d in events["host"]
                            if n[len(PREFIX):] in CALLS and self.inside(s, d))
        self._starts = [c[0] for c in self.calls]
        self.modules = sorted((s, s + d, n.split("(")[0]) for n, s, d in events["modules"])
        self._mod_starts = [m[0] for m in self.modules]
        self.busy = _union([max(s, self.t0), min(s + d, self.t1)]
                           for _, _, s, d in events["ops"] if s + d > self.t0 and s < self.t1)

    def inside(self, start: float, duration: float) -> bool:
        return self.t0 <= start and start + duration <= self.t1

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy) * 1e-9

    def count(self, kind: str) -> int:
        """Engine calls of ``kind`` inside the stretch."""
        return sum(1 for c in self.calls if c[2] == kind)

    def _find(self, starts, spans, t):
        i = bisect.bisect_right(starts, t) - 1
        return spans[i] if i >= 0 and spans[i][0] <= t <= spans[i][1] else None

    def call_at(self, t: float) -> str | None:
        """The kind of engine call running on the host at ``t``, if any."""
        c = self._find(self._starts, self.calls, t)
        return c[2] if c else None

    def module_at(self, t: float) -> str:
        m = self._find(self._mod_starts, self.modules, t)
        return m[2] if m else ""

    def module_seconds(self, kind: str) -> float:
        """Device seconds of the programs that ran inside engine calls of
        ``kind`` within the stretch."""
        return 1e-9 * sum(e - s for s, e, _ in self.modules
                          if self.inside(s, e - s) and self.call_at(s) == kind)

    def op_seconds(self, prefix: str) -> tuple[float, int]:
        """Device seconds and count of the operations named ``prefix`` or
        ``prefix.<n>`` within the stretch."""
        total, n = 0, 0
        for name, _, s, d in self.events["ops"]:
            if name.split(".")[0] == prefix and self.inside(s, d):
                total, n = total + d, n + 1
        return total * 1e-9, n

    def top_ops(self, k: int = 10) -> list:
        """The operations that took the most device time, as
        ``[program/operation, seconds]``."""
        tot = collections.Counter()
        for name, opcode, s, d in self.events["ops"]:
            if opcode not in CONTAINERS and self.inside(s, d):
                tot[f"{self.module_at(s)}/{name}"] += d * 1e-9
        return [[n, v] for n, v in tot.most_common(k)]

    def idle_gaps(self, k: int = 10) -> list:
        """Idle device time inside the stretch, summed by what the host was
        doing at each gap's middle (an engine call, or the runtime between
        them), largest first."""
        edges = [self.t0] + [x for iv in self.busy for x in iv] + [self.t1]
        tot = collections.Counter()
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                kind = self.call_at((s + e) / 2)
                tot[f"engine.{kind}" if kind else "runtime"] += (e - s) * 1e-9
        return [[n, v] for n, v in tot.most_common(k)]
