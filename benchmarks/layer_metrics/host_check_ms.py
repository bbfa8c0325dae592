"""host_check_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's parts on the tick records of the whole window:
the median, over the iterations that dispatched, of `bookkeep/check` —
the pool's invariant check (`sched.check()`) that every iteration of
every run pays. ROADMAP S4(b)'s witness (D17).
"""

from benchmarks import host_parts


def read(ctx):
    return host_parts.median_ms(ctx["ticks"], "check")
