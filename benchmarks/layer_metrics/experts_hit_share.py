"""experts_hit_share (%) — model forward; moves tokens_per_s.

Source: the engine's tick records of the whole window,
`moe_experts_hit` (held experts that at least one token of the tick
chose, summed over the expert layers): its mean over the ticks that
decoded, over held experts x expert layers. An expert nobody chose is
not computed and its weights need not be read, so this is the share of
the expert banks a tick has to stream; it rises with the rows that
decode (1 - exp(-load) under uniform routing). Nothing to read where
the program records no such counter.
"""


def read(ctx):
    hit = [t["moe_experts_hit"] for t in ctx["ticks"]
           if "moe_experts_hit" in t]
    if not hit:
        return None
    held = ctx["family"].work.held_slots(ctx["dims"])
    return 100.0 * sum(hit) / len(hit) / held
