"""host_puts_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's parts on the tick records of the whole window:
the median, over the iterations that dispatched, of the summed
`*.build/puts` parts — the programs' inputs put on the device
(`jnp.asarray` of the tokens, `jnp.int32` of a chunk's scalars).
ROADMAP S4(c)'s witness.
"""

from benchmarks import host_parts


def read(ctx):
    return host_parts.median_ms(ctx["ticks"], "puts")
