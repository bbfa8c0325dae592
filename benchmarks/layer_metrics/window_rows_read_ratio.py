"""window_rows_read_ratio (x) — model forward; moves tpot_p95_ms.

The cache rows the WINDOWED layers' reads touched in the traced ticks
(`kv_rows_read_window`, counted on the device by
serve/paged_cache.paged_forward: steps the bounded read took x rows a
step, windowed layers only) over the rows a window holds for the
requests that decoded: windowed layers x min(depth + 1, window), each
request's own depth from the tick records. 1.0 is the floor; what is
above it is the read's rounding to blocks and steps and the block a
dead slot costs. A read that did not start at the window would read
depth + 1 rows a layer and show here as depth / window. Nothing to read
where the program records no such counter.
"""

from benchmarks import tick_records


def read(ctx):
    dm = ctx["dims"]
    touched = held = 0
    for i, t, depth in tick_records.walk(ctx["ticks"]):
        if i < ctx["first_traced"] or "kv_rows_read_window" not in t:
            continue
        at = dict(depth)
        if t["prefill"]:    # the chunk runs before the tick
            _, rid, n = t["prefill"][:3]
            at[rid] = at.get(rid, 0) + n
        touched += t["kv_rows_read_window"]
        held += sum(dm["window_layout"]) * sum(
            min(at[rid] + 1, dm["window"]) for _, rid in t["decoded"])
    if not held:
        return None
    return touched / held
