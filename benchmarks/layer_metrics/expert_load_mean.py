"""expert_load_mean (tokens/expert) — model forward; moves tokens_per_s.

Source: the engine's tick records of the whole window, `moe_assignments`
(token-expert pairs this chip's held experts computed in the tick, all
expert layers together; counted on the device by
parallel/ep.moe_held_inference): the mean over the ticks that decoded
of pairs / (held experts x expert layers) — the tokens one held expert
sees in one layer of one tick. A deployment's expert sees rows x top_k
/ routed experts of ITS batch; here the batch is the 64 slots. Nothing
to read where the program records no such counter.
"""


def read(ctx):
    pairs = [t["moe_assignments"] for t in ctx["ticks"]
             if "moe_assignments" in t]
    if not pairs:
        return None
    held = ctx["family"].work.held_slots(ctx["dims"])
    return sum(pairs) / len(pairs) / held
