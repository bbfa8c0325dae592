"""The engine's phase spans (`spans` on each tick record: [phase, start,
end] in seconds on the run's clock, tiling the iteration), reduced for
the host-loop readers under layer_metrics/.

A record is one iteration of `PagedEngine.run`. The phases that matter
here by their ending: `*.dispatch` hands a program to the device,
`*.wait` is the host waiting for a program's tokens, `idle` is the
loop's own sleep; everything else is the host at work. What a sink
costs lies between one record's last span and the next record's first.

Every function returns None (or an empty list) for records without
`spans` — an engine from before the spans — so a reader built on them
reports nothing instead of a made-up number.
"""

from __future__ import annotations

import statistics


def carried(ticks) -> bool:
    return bool(ticks) and all("spans" in t for t in ticks)


def seconds(tick, pick) -> float:
    """Summed length of the record's spans whose phase `pick` accepts."""
    return sum(b - a for name, a, b in tick["spans"] if pick(name))


def is_wait(name: str) -> bool:
    return name.endswith(".wait")


def is_dispatch(name: str) -> bool:
    return name.endswith(".dispatch")


def ran(tick) -> bool:
    """The iteration handed the device a program."""
    return any(is_dispatch(name) for name, _, _ in tick["spans"])


def median_ms(ticks, pick) -> float | None:
    """Median, over the iterations that dispatched, of the time in the
    phases `pick` accepts, in ms."""
    if not carried(ticks):
        return None
    per = [seconds(t, pick) for t in ticks if ran(t)]
    return 1e3 * statistics.median(per) if per else None


def exposed(ticks) -> list[float]:
    """Per record, the host seconds the device could not overlap that
    END in it: each stretch from the end of a `*.wait` (the device has
    nothing queued once its tokens are read) to the START of the next
    `*.dispatch`, `idle` left out. A dispatch that follows another with
    no wait between (a mid-prompt chunk, then the tick) queues behind
    it and exposes nothing. The stretch crosses records: the tick's
    wait ends one iteration, the next prefill's dispatch is in the next.

    The stretch ends where the dispatch call begins and not where it
    returns: the device starts 0.1-0.2 ms into the call (the launch)
    and the rest of the call, 2.1 ms at 42 unrolled layers, runs under
    the device (PERF.md section 5.3, kept traces). So this reads under
    the device's idle gap by the launch and by the read-back at the
    other end (the tokens' way to the host, inside the `*.wait` span),
    neither of which the host's clock can see."""
    out = []
    since, slept = None, 0.0
    for t in ticks:
        total = 0.0
        for name, a, b in t["spans"]:
            if is_wait(name):
                since, slept = b, 0.0
            elif name == "idle":
                slept += b - a
            elif is_dispatch(name) and since is not None:
                total += a - since - slept
                since = None
        out.append(total)
    return out


def host_seconds(ticks, skip_gap_before=None) -> list[float]:
    """Per record, the host's own seconds: from the end of the record
    before (so a sink's time counts, to the iteration that follows it)
    to this record's end, less its `*.wait` and `idle`. The gap before
    record `skip_gap_before` is left out: the benchmark's sink starts
    the profiler there."""
    out = []
    last_end = None
    for i, t in enumerate(ticks):
        start = t["spans"][0][1]
        if last_end is not None and i != skip_gap_before:
            start = last_end
        last_end = t["spans"][-1][2]
        out.append(last_end - start
                   - seconds(t, lambda n: is_wait(n) or n == "idle"))
    return out


def excess_ms(values, usual: float, factor: float = 3.0) -> float:
    """Summed part of each value beyond `factor` x `usual`, in ms."""
    return 1e3 * sum(max(0.0, v - factor * usual) for v in values)
