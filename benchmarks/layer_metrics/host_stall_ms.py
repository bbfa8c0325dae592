"""host_stall_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's phase spans on the tick records of the whole
window. Per iteration the host's own time: from the record before to
this record's end, less `*.wait` and `idle` (host_spans.host_seconds;
the stretch in which the benchmark's sink starts the profiler is left
out). The reading is the sum, over the window, of what each iteration
spent beyond 3 x the median of the iterations that dispatched: a few
ms in a quiet window, and the lost seconds of a disturbed one if the
host is what stopped (device_stall_ms if it stopped while waiting).
"""

import statistics

from benchmarks import host_spans


def read(ctx):
    ticks = ctx["ticks"]
    if not host_spans.carried(ticks):
        return None
    own = host_spans.host_seconds(ticks, ctx["first_traced"])
    usual = [h for t, h in zip(ticks, own) if host_spans.ran(t)]
    if not usual:
        return None
    return host_spans.excess_ms(own, statistics.median(usual))
