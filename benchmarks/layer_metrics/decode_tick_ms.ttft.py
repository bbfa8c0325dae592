"""decode_tick_ms.ttft (ms) — model forward; moves ttft_p95_ms.

Source: device trace, `XLA Modules` line: the median device time of one
run of the engine's `tick` program (all slots, one token each). The
same reading as decode_tick_ms, under the name of what it moves in a
cell below capacity where TPOT is not judged: every prefill chunk of a
waiting prompt shares its iteration with one tick, so the time to the
first token is the prompt's chunks times (chunk + tick + host gap).
"""


def read(ctx):
    return ctx["trace"].module_median_ms("jit_tick")
