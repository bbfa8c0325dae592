"""host_gc_ms (ms) — serving host loop; moves tokens_per_s.

Source: the tick records' `gc_s` over the whole window: Python's
garbage collections, every generation, summed, in ms (0 where none
ran). A collection in the sink (the benchmark's keeps every record)
counts to the next record. Read beside host_stall_ms: a stall that
is a generation-2 collection is one of the record's `stops`.
"""

from benchmarks import host_parts


def read(ctx):
    ticks = ctx["ticks"]
    if not host_parts.carried(ticks, "gc_s"):
        return None
    return 1e3 * sum(sum(t["gc_s"]) for t in ticks)
