"""prefill_chunk_ms.rate (ms) — model forward; moves tokens_per_s.

Source: device trace, `XLA Modules` line: the median device time of
one run of the engine's `prefill` program (one slot, one chunk of the
prompt). The same reading has two names, one per end-to-end metric it
moves: .ttft where the time to the first token is judged, .rate where
prefill sets the tokens per second.
"""


def read(ctx):
    return ctx["trace"].module_median_ms("jit_prefill")
