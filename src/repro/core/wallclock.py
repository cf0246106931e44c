"""Wall-clock execution backend: grains run as real JAX computations.

The runtime's default ``SimBackend`` is a logical clock over modeled costs —
it can *predict* the paper's homogenization speedup but never measure one.
``WallclockBackend`` closes that gap: every grain launches a real chained
matmul workload on a real host-platform device (``jax.device_put`` pins each
worker's operand to its device; ``--xla_force_host_platform_device_count``
via ``launch/env.py`` fans one host out to N devices), and the duration that
reaches ``GrainRecord``/``worker_busy``/the ``PerformanceTracker`` heartbeat
is a *measured* wall time, not ``cost / perf``.

Heterogeneity on homogeneous devices
------------------------------------
Host-platform devices are identical, so declared worker speed is emulated by
*work volume*: a grain of cost ``c`` on a worker of declared perf ``p`` runs
``k = round(base_repeats * (c / cost_ref) / p)`` chained unit ops (one jitted
``tanh(h @ x)`` per op — the data dependency keeps the chain a single async
stream; ``tanh`` keeps magnitudes bounded at any depth).  A perf-4 worker
thus really does a quarter of a perf-1 worker's device work per grain, and
homogenized shares ∝ perf really do equalize measured busy time.  A
``perf:`` timeline event changes ``p`` mid-job, so faults slow the *device*
work, not a model.

Overlap
-------
``overlap=False`` (default) blocks on each grain at launch: per-grain
measurements are uncontended device times, so the event-loop combination of
measured durations is the fleet makespan a truly parallel deployment would
see — comparable against the simulator's prediction on any host, including
single-core CI runners.  ``overlap=True`` dispatches asynchronously and
blocks only at the completion event (``settle``), making intra-step overlap
real: while one worker's chain runs, the loop launches other workers' chains
on their devices.  Measured durations then include real device contention,
which is the honest number on a genuinely multi-core host and a pessimistic
one when devices share a core.

Everything here is plain async JAX (``jit`` + committed ``device_put``
operands + ``block_until_ready``); no Pallas kernels are involved.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any

from .runtime import ExecutionBackend, GrainExecutor, RuntimeResult

__all__ = ["WallclockBackend", "WallclockStats"]

_EPS = 1e-12
_MIN_DT = 1e-9


@dataclasses.dataclass
class WallclockStats:
    """Backend provenance attached to ``RuntimeResult.backend`` (and rolled
    into ``RunReport`` metrics by the Cluster facade)."""

    name: str                      # "wallclock"
    platform: str                  # jax backend platform ("cpu", "tpu", ...)
    n_devices: int                 # devices the backend round-robins over
    device_of: dict[str, int]      # worker -> device index (sticky)
    unit_s: float                  # calibrated seconds per unit op (EMA)
    wall_s: float                  # real wall span of the job (begin -> end)
    n_launched: int                # grains launched (>= completed under kills)
    overlap: bool

    def summary(self) -> str:
        return (
            f"{self.name}/{self.platform} x{self.n_devices}dev "
            f"unit={self.unit_s * 1e6:.1f}us wall={self.wall_s:.3f}s "
            f"launched={self.n_launched}"
            + (" overlap" if self.overlap else "")
        )


@dataclasses.dataclass(slots=True)
class _Handle:
    """One launched grain: the async result array plus its timing state."""

    value: Any                # device array at the end of the chain
    k: int                    # unit ops in the chain
    t0: float                 # perf_counter at dispatch
    measured: float | None    # wall seconds (set at launch or at settle)


class WallclockBackend(ExecutionBackend):
    """Measured execution of runtime grains on host-platform JAX devices.

    Parameters:

      side          unit-op operand is (side, side) float32 — sized so one
                    matmul dominates its dispatch overhead but stays far under
                    a millisecond on CPU,
      base_repeats  unit ops for a reference-cost grain on a perf-1.0 worker.
                    12 keeps k integral for the canonical 4:3:2:1 fleets,
      overlap       False: block at launch (uncontended measurements, see
                    module docstring).  True: async dispatch, block at the
                    completion event,
      devices       explicit jax device list (default: ``jax.devices()``);
                    workers are assigned round-robin and stick,
      calibration_reps  unit ops timed at startup to seed the unit-time EMA.
    """

    name = "wallclock"
    measured = True

    def __init__(
        self,
        *,
        side: int = 96,
        base_repeats: int = 12,
        overlap: bool = False,
        devices: list | None = None,
        calibration_reps: int = 24,
        seed: int = 0,
    ):
        try:
            import jax
            import jax.numpy as jnp
        except ImportError as e:  # pragma: no cover - jax is baked into CI
            raise RuntimeError(
                "WallclockBackend needs jax; install it or use "
                "Cluster(backend='sim')"
            ) from e
        if side < 2 or base_repeats < 1:
            raise ValueError("need side >= 2 and base_repeats >= 1")
        self._jax = jax
        self.devices = list(devices if devices is not None else jax.devices())
        if not self.devices:
            raise RuntimeError("no jax devices visible to WallclockBackend")
        self.platform = getattr(self.devices[0], "platform", "cpu")
        self.side = int(side)
        self.base_repeats = int(base_repeats)
        self.overlap = bool(overlap)
        # Chained unit op: tanh keeps values in (-1, 1) so arbitrary-depth
        # chains neither overflow nor get constant-folded away.
        self._op = jax.jit(lambda h, x: jnp.tanh(h @ x))
        x0 = jax.random.normal(
            jax.random.PRNGKey(seed), (self.side, self.side), dtype=jnp.float32
        ) / float(self.side) ** 0.5
        self._x = [jax.device_put(x0, d) for d in self.devices]
        self._dev_of: dict[str, int] = {}     # worker name -> device index
        self._next_dev = 0
        self._cost_ref = 1.0
        self._unit_s = 0.0                    # global EMA, seeded below
        self._unit_alpha = 0.3
        self._tick_ema: dict[str, float] = {}
        self._job_t0: float | None = None
        self._n_launched = 0
        self._last_stats: WallclockStats | None = None
        self._calibrate(max(int(calibration_reps), 4))

    # -- calibration ---------------------------------------------------------
    def _calibrate(self, reps: int) -> None:
        """Compile the unit op on every device and seed the unit-time EMA
        from a measured chain on device 0."""
        for x in self._x:
            self._op(x, x).block_until_ready()
        h, x = self._x[0], self._x[0]
        t0 = time.perf_counter()
        for _ in range(reps):
            h = self._op(h, x)
        h.block_until_ready()
        self._unit_s = max((time.perf_counter() - t0) / reps, _MIN_DT)

    def _learn_unit(self, dt_per_op: float) -> None:
        a = self._unit_alpha
        self._unit_s = (1.0 - a) * self._unit_s + a * max(dt_per_op, _MIN_DT)

    @property
    def unit_s(self) -> float:
        """Calibrated wall seconds per unit op (EMA over measured chains)."""
        return self._unit_s

    # -- facade helpers (known before any job runs) -------------------------
    def repeats(self, cost: float, perf: float,
                cost_ref: float | None = None) -> int:
        ref = self._cost_ref if cost_ref is None else cost_ref
        return max(1, round(
            self.base_repeats * (cost / max(ref, _EPS)) / max(perf, _EPS)
        ))

    def grain_seconds(self, cost: float, perf: float,
                      cost_ref: float | None = None) -> float:
        """Calibrated wall-time estimate for one grain — what a standalone
        run of the same grain on the same device class would measure."""
        return self.repeats(cost, perf, cost_ref) * self._unit_s

    def time_scale(self, cost_ref: float) -> float:
        """Expected wall seconds per modeled second: a grain modeled at
        ``cost / perf`` runs ``base_repeats * cost / (cost_ref * perf)`` unit
        ops, so the ratio is cost- and perf-independent.  The Cluster facade
        multiplies scenario phase estimates (and divides spec perf priors) by
        this so '@k:frac%' anchoring survives the switch to wall time."""
        return self.base_repeats * self._unit_s / max(cost_ref, _EPS)

    def step_clock(self, worker: Any) -> float:
        """Measured wall seconds per engine step for ``worker`` (EMA over
        this job's ``timed_tick``), seeded at the calibrated unit time until
        the job's first real tick lands: one engine step is one real jitted
        call, the same order of work as a unit op.  Never the modeled
        ``1/perf`` clock, which is on a different (simulated-seconds) scale
        entirely.  Wired into ``EngineExecutor.step_clock``, so the runtime's
        tick schedule and the serve heartbeats both read it."""
        return self._tick_ema.get(getattr(worker, "name", ""), self._unit_s)

    # -- device assignment ---------------------------------------------------
    def device_index(self, name: str) -> int:
        i = self._dev_of.get(name)
        if i is None:
            i = self._next_dev % len(self.devices)
            self._dev_of[name] = i
            self._next_dev += 1
        return i

    # -- ExecutionBackend: lifecycle ----------------------------------------
    def begin_job(self, executor: GrainExecutor, n_grains: int,
                  now_s: float) -> None:
        u = executor.uniform_cost
        if u is not None:
            self._cost_ref = max(float(u), _EPS)
        elif n_grains > 0:
            self._cost_ref = max(float(executor.cost(0)), _EPS)
        self._job_t0 = time.perf_counter()
        self._n_launched = 0
        # Each job learns its own step clock.  Carried over, the last job's
        # ticks (a warm-up's compiles among them) would set this job's first
        # ticks, and with them the order of its workers' steps.
        self._tick_ema.clear()

    def end_job(self, res: RuntimeResult) -> None:
        wall = (time.perf_counter() - self._job_t0) if self._job_t0 else 0.0
        self._last_stats = WallclockStats(
            name=self.name, platform=self.platform,
            n_devices=len(self.devices), device_of=dict(self._dev_of),
            unit_s=self._unit_s, wall_s=wall, n_launched=self._n_launched,
            overlap=self.overlap,
        )
        self._job_t0 = None

    def stats(self) -> WallclockStats | None:
        return self._last_stats

    # -- ExecutionBackend: modeled-path grains ------------------------------
    def launch(self, executor: GrainExecutor, worker: Any, grain: int,
               cost: float, now_s: float) -> _Handle:
        k = self.repeats(cost, getattr(worker, "perf", 1.0))
        x = self._x[self.device_index(worker.name)]
        self._n_launched += 1
        if self.tracer is not None:
            # 'start' marks the *real* device launch (the runtime's
            # 'dispatch' is the scheduling decision at the same logical t).
            self.tracer.emit("start", t_s=now_s, worker=worker.name,
                             grain=grain, repeats=k,
                             device=self.device_index(worker.name))
        t0 = time.perf_counter()
        h = x
        for _ in range(k):
            h = self._op(h, x)
        if self.overlap:
            return _Handle(h, k, t0, None)
        h.block_until_ready()
        dt = max(time.perf_counter() - t0, _MIN_DT)
        self._learn_unit(dt / k)
        return _Handle(h, k, t0, dt)

    def duration_s(self, executor: GrainExecutor, worker: Any, grain: int,
                   cost: float, now_s: float, handle: _Handle) -> float:
        if handle.measured is not None:
            return handle.measured
        # Overlap mode: schedule the completion at the calibrated estimate;
        # settle() trues it up against the real wall time.
        return handle.k * self._unit_s

    def settle(self, executor: GrainExecutor, worker: Any, grain: int,
               handle: _Handle, event_dur_s: float) -> float:
        if handle.measured is None:
            handle.value.block_until_ready()
            handle.measured = max(time.perf_counter() - handle.t0, _MIN_DT)
            self._learn_unit(handle.measured / handle.k)
        if self.tracer is not None:
            self.tracer.emit("settle", worker=worker.name, grain=grain,
                             measured_s=handle.measured,
                             modeled_s=event_dur_s)
        return handle.measured

    def observe_execute(self, worker: Any, elapsed_s: float) -> float:
        # Real per-grain compute (grad step, matmul block) is measured work.
        return elapsed_s

    # -- ExecutionBackend: incremental (engine) grains ----------------------
    def timed_tick(self, executor: GrainExecutor, worker: Any,
                   now_s: float) -> list[tuple[int, Any]]:
        t0 = time.perf_counter()
        finished = executor.tick(worker, now_s)
        dt = max(time.perf_counter() - t0, _MIN_DT)
        prev = self._tick_ema.get(worker.name)
        a = self._unit_alpha
        self._tick_ema[worker.name] = (
            dt if prev is None else (1.0 - a) * prev + a * dt
        )
        return finished
