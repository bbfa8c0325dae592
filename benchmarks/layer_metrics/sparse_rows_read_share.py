"""sparse_rows_read_share (%) — model forward; moves tpot_p95_ms.

The K/V rows the SPARSE layers' reads touched in the traced ticks
(`kv_rows_read`, counted on the device by
serve/paged_cache.paged_forward: steps the read's loop took x rows a
step, all sparse layers together) over what a read of every row would
touch for the requests that decoded: sparse layers x (depth + 1), each
request's own depth from the tick records. Under `dense_len` a query
reads every block and the share is near 100 (above it by the read's
rounding to blocks and steps and the block a dead slot costs); past it
a tick walks the union of its K/V heads' chosen blocks and the share
falls with depth. A program that computed the selection and then read
every block anyway would show 100 here at any depth. Nothing to read
where the program records no selection (`index_rows_read`) or the
family names no sparse layers.
"""

from benchmarks import tick_records


def read(ctx):
    mixers = ctx["dims"].get("mixers")
    if not mixers:
        return None
    sparse = sum(m == "attn" for m in mixers)
    touched = whole = 0
    for i, t, depth in tick_records.walk(ctx["ticks"]):
        if i < ctx["first_traced"] or "index_rows_read" not in t:
            continue
        at = dict(depth)
        if t["prefill"]:    # the chunk runs before the tick
            _, rid, n = t["prefill"][:3]
            at[rid] = at.get(rid, 0) + n
        touched += t["kv_rows_read"]
        whole += sparse * sum(at[rid] + 1 for _, rid in t["decoded"])
    if not whole:
        return None
    return 100.0 * touched / whole
