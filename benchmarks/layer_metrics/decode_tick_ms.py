"""decode_tick_ms (ms) — model forward; moves tpot_p95_ms.

Source: device trace, `XLA Modules` line: the median device time of one
run of the engine's `tick` program (all slots, one token each).
"""


def read(ctx):
    return ctx["trace"].module_median_ms("jit_tick")
