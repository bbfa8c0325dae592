"""device_idle_share (%) — device; moves tokens_per_s.

Source: device trace, `XLA Ops` line: 1 - (union of the intervals in
which an operation ran) / (the traced slice, first operation to last).
"""


def read(ctx):
    trace = ctx["trace"]
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
