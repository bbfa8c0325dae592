"""Profiling hooks.

The reference has none (SURVEY.md §5.1: no timers, no NVTX, no cudaEvent).
Here: a wall-clock step timer that understands JAX async dispatch — and
now attributes the wall-clock to PHASES (host data prep, async dispatch,
device-compute wait, checkpointing), the split bench.py used to estimate
by hand — plus a context manager around jax.profiler for device traces
viewable in TensorBoard/XProf.
"""

from __future__ import annotations

import contextlib
import time

import jax

# Canonical phase names (the "step_phases" record's phases_ms keys).
# data:       host-side batch assembly (indexing, normalize, device_put)
# dispatch:   time inside the jitted call before it returns (async: this
#             is tracing/enqueue, NOT device compute)
# device:     waiting on device completion at sync points (block/fetch)
# checkpoint: snapshot + enqueue of checkpoint saves
STEP_PHASES = ("data", "dispatch", "device", "checkpoint")


class StepTimer:
    """Accumulates per-step wall-clock, optionally attributed to phases.

    Call block_until_ready on the step output before stop() — JAX
    dispatch is async and returns before the TPU finishes. Phase usage:

        timer.start()
        with timer.phase("data"):     bx, by = make_batch()
        with timer.phase("dispatch"): state, m = step(state, bx, by)
        with timer.phase("device"):   jax.block_until_ready(state)
        timer.stop(n_steps)

    Phases nest with the start/stop envelope, not with each other.

    `clock` has the time.perf_counter call shape; fault-harness tests
    drive it with a faults.FakeClock so telemetry assertions are
    deterministic — the timer itself never reads wall time elsewhere.
    """

    def __init__(self, *, clock=None):
        self._clock = clock if clock is not None else time.perf_counter
        self.reset()

    def reset(self) -> None:
        """Zero all counters (a fresh timer without reallocating)."""
        self.steps = 0
        self.total_s = 0.0
        self.excluded_s = 0.0
        self.phase_s: dict[str, float] = {}
        self._t0 = None

    def start(self) -> None:
        self._t0 = self._clock()

    def stop(self, n_steps: int = 1) -> float:
        if self._t0 is None:
            raise RuntimeError(
                "StepTimer.stop() before start() — call start() at the "
                "top of the timed region (or reset() after an aborted one)"
            )
        dt = self._clock() - self._t0
        self._t0 = None
        self.steps += n_steps
        self.total_s += dt
        return dt

    @contextlib.contextmanager
    def phase(self, name: str):
        """Attribute the enclosed wall-clock to `name` (accumulates)."""
        t0 = self._clock()
        try:
            yield
        finally:
            self.phase_s[name] = (
                self.phase_s.get(name, 0.0) + self._clock() - t0
            )

    @contextlib.contextmanager
    def exclude(self):
        """Remove the enclosed wall-clock from the running envelope (by
        shifting the start mark forward) — for one-off work inside the
        timed region that must not pollute the per-step attribution,
        e.g. the obs cost-analysis AOT compile. The cumulative total is
        kept in `excluded_s` so callers can subtract it from their own
        independent wall-clocks too."""
        t0 = self._clock()
        try:
            yield
        finally:
            dt = self._clock() - t0
            self.excluded_s += dt
            if self._t0 is not None:
                self._t0 += dt

    def add(self, seconds: float, n_steps: int = 1) -> None:
        """Fold an externally measured interval into the accumulators —
        for callers aggregating sub-timers that already excluded what
        must not count (e.g. Trainer.train over run_epoch's seconds)."""
        self.total_s += seconds
        self.steps += n_steps

    @property
    def mean_step_ms(self) -> float:
        return 1000.0 * self.total_s / max(self.steps, 1)

    def phases_ms(self) -> dict[str, float]:
        """Mean per-step milliseconds by phase, plus the unattributed
        remainder as "other" (total envelope minus the phase sum)."""
        n = max(self.steps, 1)
        out = {k: round(1000.0 * v / n, 4) for k, v in self.phase_s.items()}
        other = self.total_s - sum(self.phase_s.values())
        if self.phase_s and other > 0:
            out["other"] = round(1000.0 * other / n, 4)
        return out


@contextlib.contextmanager
def profile_trace(logdir: str | None):
    """Capture a device trace with jax.profiler when logdir is set."""
    if not logdir:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
