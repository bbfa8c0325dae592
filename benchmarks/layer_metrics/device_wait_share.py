"""device_wait_share (%) — serving host loop; moves tokens_per_s.

Source: the engine's phase spans on the tick records of the whole
window: the summed `*.wait` spans (the host waiting for a program's
tokens) over the timed call's seconds. It is the share of the window
the host had nothing to do but wait, over the whole window where
device_idle_share sees the last seconds only. It is NOT the device's
busy share. Bias: it leaves out the device time the host works under
(the dispatch call's tail, `grow`, `tick.build` and `tick.dispatch`
behind a mid-prompt chunk: 5.2 ms an iteration in generation, 1.6 in
chat) and counts the tokens' read-back, when the device is already
idle, as waiting (2.5 / 1.4 ms), so it reads 0.5-4.5 points under
100 - device_idle_share (PERF.md section 6). More host work hidden
under the device lowers it and harms nothing; a host that exposes more
lowers it too, and that is what it is for.
"""

from benchmarks import host_spans


def read(ctx):
    ticks = ctx["ticks"]
    if not host_spans.carried(ticks):
        return None
    waited = sum(host_spans.seconds(t, host_spans.is_wait) for t in ticks)
    return 100.0 * waited / ctx["window_s"]
