"""tick_roofline.window (%) — model forward; moves tpot_p95_ms.

`tick_roofline` for a model with windowed layers beside global ones:
the least time the chip could take for the decode ticks of the traced
slice over the time their `tick` program took on the device. Least
time, a tick, by the family's `work.window_tick_least_seconds`, which
takes EACH decoding request's own context (depth + 1, from the tick
records): the larger of operations over the bf16 peak and bytes over
the HBM peak, where the bytes are every weight outside the expert banks,
the banks of the experts the tick's counters say were touched
(`moe_experts_hit`), and each request's live K and V rows once a layer
-- all of them in a global layer, at most a window's in a windowed one
-- and the operations are those of the live rows, of the pairs the
counters say were computed (`moe_assignments`) and of attention over
those same rows. Device time: the summed runs of `jit_tick` in the
slice; the records pair off with the runs from the end of the window,
as `tick_roofline` and run.label_gaps pair them. Nothing to read where
the family has no such count or the program no such counters.
"""

from benchmarks import tick_records


def read(ctx):
    work = ctx["family"].work
    if not hasattr(work, "window_tick_least_seconds"):
        return None
    runs = ctx["trace"].module_durations("jit_tick")
    ticks = []
    for i, t, depth in tick_records.walk(ctx["ticks"]):
        if i >= ctx["first_traced"] and "moe_assignments" in t:
            at = dict(depth)
            if t["prefill"]:    # the chunk runs before the tick
                _, rid, n = t["prefill"][:3]
                at[rid] = at.get(rid, 0) + n
            # A decoded token attends to its cache and to itself.
            ticks.append(([at[rid] + 1 for _, rid in t["decoded"]],
                          t["moe_assignments"], t["moe_experts_hit"]))
    n = min(len(runs), len(ticks))
    if not n:
        return None
    bytes_each = {"float32": 4, "bfloat16": 2}
    cfg = ctx["config"]
    least = sum(
        work.window_tick_least_seconds(
            ctx["dims"], ctx["peaks"], contexts=contexts, assignments=pairs,
            experts_hit=hit, weight_bytes=bytes_each[cfg["weights_dtype"]],
            cache_bytes=bytes_each[cfg["cache_dtype"]])
        for contexts, pairs, hit in ticks[-n:])
    return 100.0 * least / sum(runs[-n:])
