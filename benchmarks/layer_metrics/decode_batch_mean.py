"""decode_batch_mean (slots) — serving host loop; moves tokens_per_s.

Source: the engine's tick records (`tick_sink`) of the whole window:
the mean number of slots that decoded, over the iterations that decoded
at all. It is the batch the decode tick's fixed cost is shared over.
"""


def read(ctx):
    sizes = [len(t["decoded"]) for t in ctx["ticks"] if t["decoded"]]
    return sum(sizes) / len(sizes) if sizes else None
