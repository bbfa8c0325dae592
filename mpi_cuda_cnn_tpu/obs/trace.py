"""Tracing spans: one name, visible in BOTH XProf and the JSONL stream.

Three tools, one naming scheme:

- `annotate(name)` — for code under `jax.jit`/`shard_map` tracing: a
  `jax.named_scope` so the region's HLO ops carry the name into XProf /
  TensorBoard device traces. Zero runtime cost (it is metadata on the
  traced ops).
- `span(name, metrics=...)` — for HOST-side regions (epoch loops, eval
  sweeps, checkpoint saves): nests via a stack, wraps
  `jax.profiler.TraceAnnotation` so the host track of an XProf capture
  shows the same name, and on exit emits a {"event": "span"} record to
  the metrics stream. XProf traces and the JSONL therefore agree on
  names — the point of pillar (1) in the obs design.
- `PhaseSpans(prefix, ...)` — for a HOST LOOP whose every iteration is
  a run of phases (the serving engine's `run`): the loop marks phase
  boundaries on it, it keeps `[phase, start, end]` on the loop's own
  clock for the iteration's record and brackets each phase with a
  `TraceAnnotation` named `<prefix>/<phase>`, so the record and the
  profiler's host track carry the same spans.

Span names compose with '/' as they nest: span("epoch") containing
span("eval") emits "epoch/eval". Host spans measure wall-clock only;
they do NOT force device completion (a span around an async dispatch
measures the dispatch, which is exactly the async split StepTimer
accounts for).
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax

_state = threading.local()


def _stack() -> list[str]:
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


def current_path() -> str:
    """The '/'-joined path of open host spans on this thread ('' at top)."""
    return "/".join(_stack())


def annotate(name: str):
    """Named scope for traced code — the in-jit half of the span API."""
    return jax.named_scope(name)


@contextlib.contextmanager
def span(name: str, metrics=None, **fields):
    """Host-side named span. Emits one "span" record on exit when a
    metrics logger (utils.logging.MetricsLogger) is passed; always
    annotates the profiler's host track so an XProf capture taken over
    the region shows the same name."""
    stack = _stack()
    stack.append(name)
    path = "/".join(stack)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(path):
            yield path
    finally:
        dt_ms = (time.perf_counter() - t0) * 1e3
        popped = stack.pop()
        assert popped == name
        if metrics is not None:
            metrics.log("span", name=path, ms=round(dt_ms, 3), **fields)


class PhaseSpans:
    """The phases of a host loop's iterations, back to back: `begin`
    opens an iteration with its first phase, `enter` ends the open
    phase and starts the next at the same stamp, `end` closes the
    iteration and hands its spans over. Every span is `[phase, start,
    end]` in seconds since `t0` on `time_fn`'s clock and lies under a
    `jax.profiler.TraceAnnotation` named `<prefix>/<phase>` whose
    argument `tick` is the iteration's index. `close` ends the open
    phase of an iteration that is left unfinished.

    A boundary the loop has already read the clock for is passed as
    `t`; the recorder reads the clock itself only where none is given,
    so a loop that builds no recorder makes no extra read."""

    def __init__(self, prefix: str, time_fn=time.perf_counter,
                 t0: float = 0.0):
        self.prefix = prefix
        self._time_fn, self._t0 = time_fn, t0
        self._tick = 0
        self._spans: list[list] = []
        self._ann = None

    def begin(self, tick: int, phase: str, t: float | None = None) -> None:
        self._tick = tick
        self.enter(phase, t)

    def enter(self, phase: str, t: float | None = None) -> None:
        if t is None:
            t = self._time_fn() - self._t0
        self._close(t)
        self._spans.append([phase, round(t, 6), None])
        self._ann = jax.profiler.TraceAnnotation(
            f"{self.prefix}/{phase}", tick=self._tick)
        self._ann.__enter__()

    def end(self) -> list[list]:
        """Close the open phase now; the iteration's spans, in order."""
        self.close()
        spans, self._spans = self._spans, []
        return spans

    def close(self) -> None:
        """Close the open phase now, if one is open: what a loop that
        leaves mid-iteration (an exception) owes the profiler."""
        if self._ann is not None:
            self._close(self._time_fn() - self._t0)

    def _close(self, t: float) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
            self._spans[-1][2] = round(t, 6)
