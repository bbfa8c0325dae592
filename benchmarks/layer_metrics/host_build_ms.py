"""host_build_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's phase spans on the tick records of the whole
window: the median, per iteration that dispatched, of `*.build` +
`*.dispatch` + `grow` — choosing the slots, the numpy inputs, their
puts to the device and the calls of the jitted programs.
"""

from benchmarks import host_spans


def read(ctx):
    return host_spans.median_ms(
        ctx["ticks"], lambda n: n == "grow" or n.endswith(".build")
        or host_spans.is_dispatch(n))
