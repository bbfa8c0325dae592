"""The engine's parts and stops (ISSUE 36), reduced for the host-loop
readers under layer_metrics/ that name what a phase span is made of.

On each tick record, beside `spans` (host_spans.py):

- `parts`: `[f"{phase}/{part}", start, end]`, pieces of a phase timed
  where they run, on the spans' clock — `*.build/tables` (the block
  tables and the cache view), `*.build/puts` (the inputs' puts),
  `*.wait/fetch` (the copy of tokens already ready), `bookkeep/check`
  (the pool check);
- `gc_s`: the iteration's seconds of garbage collection, generation
  0, 1 and 2 (a collection in the sink counts to the next record);
- `stops`: `["gc", start, end, 2]` for each generation-2 collection,
  `["compile", start, end, "<stage>:<fun_name>"]` for each jax trace,
  lowering and backend compile, on the same clock.

Every function returns None for records without the field — an engine
from before the parts — so a reader reports nothing instead of a
made-up number.
"""

from __future__ import annotations

import statistics

from benchmarks import host_spans


def carried(ticks, field: str) -> bool:
    return host_spans.carried(ticks) and all(field in t for t in ticks)


def part_seconds(tick, part: str) -> float:
    """Summed length of the record's parts named `<phase>/<part>`."""
    return sum(b - a for name, a, b in tick["parts"]
               if name.rsplit("/", 1)[-1] == part)


def median_ms(ticks, part: str, among=host_spans.ran) -> float | None:
    """Median, over the records `among` accepts (by default those that
    dispatched), of the time in parts `part`, in ms."""
    if not carried(ticks, "parts"):
        return None
    per = [part_seconds(t, part) for t in ticks if among(t)]
    return 1e3 * statistics.median(per) if per else None


def waited(tick) -> bool:
    """The iteration read a program's tokens (a `*.wait` span)."""
    return any(host_spans.is_wait(name) for name, _, _ in tick["spans"])


def stop_ms(ticks, kind: str) -> float | None:
    """Length of the union of the `kind` stops on the records, clipped
    to [the first record's first span, the last record's end], in ms:
    a trace nested in another's counts once."""
    if not carried(ticks, "stops"):
        return None
    lo, hi = ticks[0]["spans"][0][1], ticks[-1]["spans"][-1][2]
    spans = sorted((max(a, lo), min(b, hi)) for t in ticks
                   for k, a, b, _ in t["stops"]
                   if k == kind and a < hi and b > lo)
    total, reach = 0.0, lo
    for a, b in spans:
        if b > reach:
            total += b - max(a, reach)
            reach = b
    return 1e3 * total
