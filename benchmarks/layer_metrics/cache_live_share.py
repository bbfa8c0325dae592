"""cache_live_share (%) — serving host loop; moves tokens_per_s.

Source: the engine's tick records of the whole window: the tokens that
requests in flight hold in the paged cache, over the tokens the pool
reserves (slots x max_len), averaged over the iterations. The program's
tick reads or converts the whole reservation whatever it holds, so this
says how much of that cost serves a request; read it beside
`memory_peak_bytes`, which counts the reservation whole.
"""

from benchmarks import tick_records


def read(ctx):
    held = [sum(depth.values())
            for _, _, depth in tick_records.walk(ctx["ticks"])]
    if not held:
        return None
    return 100.0 * sum(held) / len(held) / (ctx["slots"] * ctx["max_len"])
