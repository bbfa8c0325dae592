"""programs_compiled_in_window (count) — serving host loop; moves tokens_per_s.

Source: the tick records' `compiled`: the forms the engine's jitted
programs have compiled since the run began, counted from before its
first dispatch. The reading is the last record's count, less the first
record's where the records begin after the run's first iteration (what
that iteration compiled cannot be told then). A program compiled
inside the timed window shows as a step, the window's first iteration
included: a prefill bucket the warm-up missed; 0 is what the warm-up
is for.
"""


def read(ctx):
    ticks = ctx["ticks"]
    if not ticks or "compiled" not in ticks[0] or "compiled" not in ticks[-1]:
        return None
    before = ticks[0]["compiled"] if ticks[0].get("tick", 0) else 0
    return ticks[-1]["compiled"] - before
