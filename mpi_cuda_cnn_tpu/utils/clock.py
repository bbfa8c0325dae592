"""The ONE sanctioned wall-clock surface (`mctpu lint` MCT002).

Every duration this framework measures goes through an injectable
`clock` parameter with the time.perf_counter call shape — FakeClock
substitutes it and the serving/fleet/elasticity proofs are bitwise-
deterministic because of it. One capability genuinely needs the REAL
wall clock and has no business being injectable:

- `utc_stamp()` — a human-readable absolute timestamp for run-boundary
  markers (utils/logging.py's `# run 2026-...` line). Record "t"
  fields stay relative (the schema's cross-process contract); the
  marker is documentation for a human scanning an append-mode file.
- `epoch_seconds()` — the clock jax stamps its compile events with
  (`jax.monitoring` time spans): read once, beside the injected clock,
  to map those stamps onto it (obs/trace.PhaseSpans.watch).

Raw `time.time` / `time.monotonic` / `datetime.now` reads anywhere
else are MCT002 findings: either the caller should take an injectable
clock, or its need belongs here with a name and a docstring — or, for
code that cannot import this package at all (bench.py's parent process
must never trigger the jax import chain), a commented
`# mctpu: disable=MCT002` at the site. The analyzer's manifest
(ci/lint_manifest.json clock_modules) allowlists exactly this file.
"""

from __future__ import annotations

import time

__all__ = ["epoch_seconds", "utc_stamp"]


def utc_stamp(fmt: str = "%Y-%m-%dT%H:%M:%SZ") -> str:
    """The current UTC moment, formatted. For run markers and file
    names only — never for measuring durations (inject a clock) and
    never into record "t" fields (those are relative by schema)."""
    return time.strftime(fmt, time.gmtime())


def epoch_seconds() -> float:
    """`time.time()`: what jax.monitoring stamps a compile's start and
    end with. Only to map such stamps onto an injected clock — never
    to measure a duration of this program's own."""
    return time.time()
