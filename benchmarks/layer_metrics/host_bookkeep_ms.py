"""host_bookkeep_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's phase spans on the tick records of the whole
window: the median, per iteration that dispatched, of `emit` +
`bookkeep` — tokens to requests, finished requests, the drains, the
state digest and the pool check that every run pays. `record` is left
out: it is the tracing's own cost and a run with no sink has none.
"""

from benchmarks import host_spans


def read(ctx):
    return host_spans.median_ms(ctx["ticks"],
                                lambda n: n in ("emit", "bookkeep"))
