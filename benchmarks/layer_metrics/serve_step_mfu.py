"""serve_step_mfu (%) — model forward; moves tokens_per_s.

The whole step's share of the chip's peak: model FLOPs (the family's
work.py: 2 per matmul parameter per token, attention over each token's
own context) of
every token the WHOLE WINDOW processed, prompt and output alike, over
the window's seconds (the timed call's, as `tokens_per_s` has them) x
chips x the bf16 peak (peaks.json). Which tokens those were comes from
the engine's tick records. Decode is bound by bytes, so this reads low
where decode does the work: that is what it should say.
"""

from benchmarks import tick_records


def read(ctx):
    dm, span_flops = ctx["dims"], ctx["family"].work.span_flops
    flops = 0
    for _, t, depth in tick_records.walk(ctx["ticks"]):
        at = dict(depth)
        if t["prefill"]:
            _, rid, n = t["prefill"][:3]
            flops += span_flops(dm, at.get(rid, 0), n)
            at[rid] = at.get(rid, 0) + n
        for _, rid in t["decoded"]:
            flops += span_flops(dm, at[rid], 1)
    if not flops:
        return None
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * flops / (ctx["window_s"] * peak)
