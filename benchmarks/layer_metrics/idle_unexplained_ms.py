"""idle_unexplained_ms (ms) — serving host loop; moves tokens_per_s.

Source: the device trace against the engine's phase spans, traced
slice only. The device's idle seconds (the slice, first operation to
last, less the busy union) less the exposed host seconds of the traced
records (host_spans.exposed over ticks[first_traced:]: the stretches
that begin and end inside the slice), over the iterations that have
such a stretch. What is left is the idle no span on the host's clock
covers: the read-back before a `*.wait` returns, the launch after a
`*.dispatch` begins, the gaps between a program's own operations. It
is a residual: with host_exposed_ms it sums to the device's mean gap by
construction, so it checks nothing about where the spans sit; it sizes
what a perf PR on the read-back or the launch could win.
"""

from benchmarks import host_spans


def read(ctx):
    ticks = ctx["ticks"][ctx["first_traced"]:]
    if not host_spans.carried(ticks):
        return None
    per = [x for x in host_spans.exposed(ticks) if x > 0.0]
    if not per:
        return None
    trace = ctx["trace"]
    return 1e3 * (trace.window_s - trace.busy_s - sum(per)) / len(per)
