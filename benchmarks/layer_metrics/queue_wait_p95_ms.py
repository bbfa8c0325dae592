"""queue_wait_p95_ms (ms) — serving host loop; moves ttft_p95_ms.

Source: the request records of the traced run: admitted - due, 95th
percentile by nearest rank over the requests admitted BEFORE the
profiler was switched on (its start stalls the loop for a moment; the
records up to there cost only the tick sink).
"""


from benchmarks.percentile import pct_nearest


def read(ctx):
    ticks, first = ctx["ticks"], ctx["first_traced"]
    before = ticks[first - 1]["now"] if first else 0.0
    return pct_nearest([1e3 * (r.admitted_at - r.arrival)
                        for r in ctx["requests"]
                        if r.admitted_at is not None
                        and r.admitted_at <= before], 95)
