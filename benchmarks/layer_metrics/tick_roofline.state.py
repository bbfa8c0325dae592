"""tick_roofline.state (%) — model forward; moves tpot_p95_ms.

`tick_roofline` for a model whose softmax layers select the blocks
they read and whose linear layers keep a state a slot: the least time
the chip could take for the decode ticks of the traced slice over the
time their `tick` program took on the device. Least time, a tick, by
the family's `work.state_tick_least_seconds`: the larger of operations
over the bf16 peak and bytes over the HBM peak, where the bytes are
every weight once, each decoding request's states read and written
once a linear layer (f32), the compressed keys the selections scored
(`index_rows_read`) and the K and V rows the reads touched
(`kv_rows_read`) -- both the tick's own counters, so the numerator
counts no byte the program did not move -- and the operations are
those of the decoding requests' rows at each request's own context
(depth + 1, from the tick records). Device time: the summed runs of
`jit_tick` in the slice; the records pair off with the runs from the
end of the window, as `tick_roofline` and run.label_gaps pair them.
Nothing to read where the family has no such count or the program no
such counters.
"""

from benchmarks import tick_records


def read(ctx):
    work = ctx["family"].work
    if not hasattr(work, "state_tick_least_seconds"):
        return None
    runs = ctx["trace"].module_durations("jit_tick")
    ticks = []
    for i, t, depth in tick_records.walk(ctx["ticks"]):
        if i >= ctx["first_traced"] and "index_rows_read" in t:
            at = dict(depth)
            if t["prefill"]:    # the chunk runs before the tick
                _, rid, n = t["prefill"][:3]
                at[rid] = at.get(rid, 0) + n
            # A decoded token attends to its cache and to itself.
            ticks.append(([at[rid] + 1 for _, rid in t["decoded"]],
                          t["kv_rows_read"], t["index_rows_read"]))
    n = min(len(runs), len(ticks))
    if not n:
        return None
    bytes_each = {"float32": 4, "bfloat16": 2}
    cfg = ctx["config"]
    least = sum(
        work.state_tick_least_seconds(
            ctx["dims"], ctx["peaks"], contexts=contexts, kv_rows_read=rows,
            index_rows_read=scored,
            weight_bytes=bytes_each[cfg["weights_dtype"]],
            cache_bytes=bytes_each[cfg["cache_dtype"]])
        for contexts, rows, scored in ticks[-n:])
    return 100.0 * least / sum(runs[-n:])
