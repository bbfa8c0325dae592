"""tick_roofline (%) — model forward; moves tpot_p95_ms.

The least time the chip could take for the decode ticks of the traced
slice over the time their `tick` program took on the device. Least
time, a tick, by the family's `work.tick_least_seconds`: the larger of
operations over the bf16 peak and bytes over the HBM peak, where the
bytes are every weight outside the expert banks, the banks of the
experts the tick's counters say were touched (`moe_experts_hit`), and
the LIVE latent rows once a layer (each decoding request's own depth,
from the tick records — not the rows the read touched,
`latent_rows_read`, which is what the program really moved), and the
operations are those of the live rows, of the pairs the counters say
were computed (`moe_assignments`) and of attention over each request's
own context. Device time: the summed runs of `jit_tick` in the slice;
the records pair off with the runs from the end of the window, as
run.label_gaps pairs them. Nothing to read where the program records
no such counters (a family whose work.py has no tick count has none).
"""

from benchmarks import tick_records


def read(ctx):
    work = ctx["family"].work
    if not hasattr(work, "tick_least_seconds"):
        return None
    trace = ctx["trace"]
    runs = trace.module_durations("jit_tick")
    ticks = []
    for i, t, depth in tick_records.walk(ctx["ticks"]):
        if i >= ctx["first_traced"] and "moe_assignments" in t:
            at = dict(depth)
            if t["prefill"]:    # the chunk runs before the tick
                _, rid, n = t["prefill"][:3]
                at[rid] = at.get(rid, 0) + n
            rids = [rid for _, rid in t["decoded"]]
            # A decoded token attends to its cache and to itself.
            ticks.append((len(rids), sum(at[r] + 1 for r in rids),
                          t["moe_assignments"], t["moe_experts_hit"]))
    n = min(len(runs), len(ticks))
    if not n:
        return None
    bytes_each = {"float32": 4, "bfloat16": 2}
    cfg = ctx["config"]
    least = sum(
        work.tick_least_seconds(
            ctx["dims"], ctx["peaks"], rows=rows, contexts=contexts,
            assignments=pairs, experts_hit=hit,
            weight_bytes=bytes_each[cfg["weights_dtype"]],
            cache_bytes=bytes_each[cfg["cache_dtype"]])
        for rows, contexts, pairs, hit in ticks[-n:])
    return 100.0 * least / sum(runs[-n:])
