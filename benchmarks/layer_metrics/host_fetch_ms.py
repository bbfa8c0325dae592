"""host_fetch_ms (ms) — serving host loop; moves tokens_per_s.

Source: the engine's parts on the tick records of the whole window:
the median, over the iterations that read a program's tokens (a
`*.wait` span), of their `*.wait/fetch` parts — the copy to the host
of tokens the device has already made (`np.asarray(nxt)` / `int(nxt)`
after `block_until_ready`). The rest of `*.wait` is the wait for the
ready notice. ROADMAP S4(a)'s witness.
"""

from benchmarks import host_parts


def read(ctx):
    return host_parts.median_ms(ctx["ticks"], "fetch",
                                among=host_parts.waited)
