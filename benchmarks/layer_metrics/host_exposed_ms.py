"""host_exposed_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's phase spans on the tick records of the whole
window (host_spans.py). Median, over the iterations that dispatched a
program, of the host time the device cannot overlap: from the end of a
`*.wait` span (the host has the tokens, the device has nothing queued)
to the start of the next `*.dispatch` span, the loop's own `idle` sleep
left out. The dispatch call itself is left out: the device starts
0.1-0.2 ms into it and the rest runs under the device, so shortening
the call's tail moves nothing end to end. Bias: under the idle gap the
device trace shows before a program by that launch and by the
read-back of the tokens (idle_unexplained_ms reads the two together).
"""

import statistics

from benchmarks import host_spans


def read(ctx):
    ticks = ctx["ticks"]
    if not host_spans.carried(ticks):
        return None
    per = [x for t, x in zip(ticks, host_spans.exposed(ticks))
           if host_spans.ran(t)]
    return 1e3 * statistics.median(per) if per else None
