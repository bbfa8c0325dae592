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
  profiler's host track carry the same spans. A phase's named PARTS
  (`part`) and, while it `watch`es, the run's garbage collections and
  jax compiles (its STOPS) ride the same record.

Span names compose with '/' as they nest: span("epoch") containing
span("eval") emits "epoch/eval". Host spans measure wall-clock only;
they do NOT force device completion (a span around an async dispatch
measures the dispatch, which is exactly the async split StepTimer
accounts for).
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time

import jax

from ..utils.clock import epoch_seconds

_state = threading.local()

# jax.monitoring's time spans of a trace, a lowering and a backend
# compile: `<this><stage>_duration`, stage one of `jaxpr_trace`,
# `jaxpr_to_mlir_module`, `backend_compile`.
COMPILE_EVENTS = "/jax/core/compile/"
_NO_PART = contextlib.nullcontext()


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
    so a loop that builds no recorder makes no extra read.

    Inside a phase, `part(name)` times a piece of its work as
    `[f"{phase}/{name}", start, end]` under a nested annotation
    `<prefix>/<phase>/<name>`; `fetch` reads a device result as part
    `fetch` once it is ready. Between `watch` and `unwatch` every
    garbage collection adds its seconds to its generation's sum, and
    each generation-2 collection (`["gc", start, end, 2]`, annotated
    `<name>.gc/2`) and each jax trace, lowering and backend compile
    (`["compile", start, end, "<stage>:<fun_name>"]`) is a stop. What
    came since the last hand-over leaves with `extras()`."""

    def __init__(self, prefix: str, time_fn=time.perf_counter,
                 t0: float = 0.0):
        self.prefix = prefix
        self._time_fn, self._t0 = time_fn, t0
        self._tick = 0
        self._spans: list[list] = []
        self._ann = None
        self._parts: list[list] = []
        self._stops: list[list] = []
        self._gc_s = [0.0, 0.0, 0.0]
        self._gc_at, self._gc_ann = 0.0, None
        self._hooks = None      # watch's (gc callback, compile listener)

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

    def _now(self) -> float:
        return self._time_fn() - self._t0

    @contextlib.contextmanager
    def part(self, name: str):
        """Time the block as part `name` of the open phase."""
        label = f"{self._spans[-1][0]}/{name}"
        start = self._now()
        try:
            with jax.profiler.TraceAnnotation(f"{self.prefix}/{label}",
                                              tick=self._tick):
                yield
        finally:
            self._parts.append([label, round(start, 6),
                                round(self._now(), 6)])

    def fetch(self, x, read):
        """`read(x)` of a device result: first the wait until `x` is
        ready (the phase's own time), then the read itself — the copy
        to the host of what is already there — as part `fetch`."""
        jax.block_until_ready(x)
        with self.part("fetch"):
            return read(x)

    def extras(self) -> dict:
        """What came since the last hand-over: `parts` in the order
        they ended, `gc_s` (seconds collecting, by generation) and
        `stops` in the order they were heard."""
        out = {"parts": self._parts, "gc_s": [round(s, 6) for s in self._gc_s],
               "stops": self._stops}
        self._parts, self._stops, self._gc_s = [], [], [0.0, 0.0, 0.0]
        return out

    def watch(self, name: str) -> None:
        """Hear every garbage collection and jax compile until
        `unwatch`. jax stamps a compile on the epoch's clock: one pair
        of reads back to back maps it onto the recorder's."""
        self._epoch_to_run = self._now() - epoch_seconds()
        self._gc_name = f"{name}.gc/2"
        self._hooks = (self._on_gc, self._on_compile)
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_time_span_listener(self._on_compile)

    def unwatch(self) -> None:
        """Take `watch`'s hooks down, if they are up."""
        if self._hooks is not None:
            on_gc, on_compile = self._hooks
            self._hooks = None
            gc.callbacks.remove(on_gc)
            jax.monitoring.unregister_event_time_span_listener(on_compile)

    def _on_gc(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        if phase == "start":
            self._gc_at = self._now()
            if gen == 2:
                self._gc_ann = jax.profiler.TraceAnnotation(self._gc_name)
                self._gc_ann.__enter__()
            return
        start, end = self._gc_at, self._now()
        self._gc_s[gen] += end - start
        if gen == 2:
            self._gc_ann.__exit__(None, None, None)
            self._stops.append(["gc", round(start, 6), round(end, 6), 2])

    def _on_compile(self, event: str, start: float, end: float,
                    **kwargs) -> None:
        if not event.startswith(COMPILE_EVENTS):
            return
        stage = event[len(COMPILE_EVENTS):].removesuffix("_duration")
        shift = self._epoch_to_run
        self._stops.append(["compile", round(start + shift, 6),
                            round(end + shift, 6),
                            f"{stage}:{kwargs.get('fun_name', '')}"])


def part(spans: PhaseSpans | None, name: str):
    """`spans.part(name)`, or a block that does nothing where no
    recorder listens: the bare loop reads no clock for it."""
    return _NO_PART if spans is None else spans.part(name)
