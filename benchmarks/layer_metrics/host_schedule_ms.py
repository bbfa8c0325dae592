"""host_schedule_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's phase spans on the tick records of the whole
window: the median `schedule` span (injected faults, the expiry sweep,
admission, the queue bound) over the iterations that dispatched.
"""

from benchmarks import host_spans


def read(ctx):
    return host_spans.median_ms(ctx["ticks"], lambda n: n == "schedule")
