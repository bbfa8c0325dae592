"""host_tables_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's parts on the tick records of the whole window:
the median, over the iterations that dispatched, of the summed
`*.build/tables` parts — the block tables the host builds whole at
every dispatch (`engine._tables`) and the cache view that puts them
(`engine._cache_view`). ROADMAP S4(d)'s witness.
"""

from benchmarks import host_parts


def read(ctx):
    return host_parts.median_ms(ctx["ticks"], "tables")
