"""device_stall_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's phase spans on the tick records of the whole
window. Per iteration the summed `*.wait`; the reading is the sum, over
the window, of what each iteration waited beyond 3 x the median of the
iterations that ran the same programs (the same `*.dispatch` and
`*.wait` spans in the same order: a chunk and a tick, a tick alone, a
completing chunk). The lost seconds of a disturbed window show here if
they passed while the host waited on the device, in host_stall_ms if
they passed in the host's own code.
"""

import statistics

from benchmarks import host_spans


def read(ctx):
    ticks = ctx["ticks"]
    if not host_spans.carried(ticks):
        return None
    groups: dict[tuple, list[float]] = {}
    for t in ticks:
        programs = tuple(n for n, _, _ in t["spans"]
                         if host_spans.is_dispatch(n) or host_spans.is_wait(n))
        if programs:
            groups.setdefault(programs, []).append(
                host_spans.seconds(t, host_spans.is_wait))
    if not groups:
        return None
    return sum(host_spans.excess_ms(w, statistics.median(w))
               for w in groups.values())
