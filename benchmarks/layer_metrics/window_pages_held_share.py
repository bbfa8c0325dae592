"""window_pages_held_share (%) — serving host loop; moves tokens_per_s.

Source: the engine's tick records of the whole window, `pages_held`
([global group, windowed group]: pages issued at the iteration's end):
the mean over the iterations of the windowed group's pages over the
pages ONE table for all layers would pin for the same slots, the sum of
pages_for(depth) over the requests in flight (depth after the
iteration, from the records; the page size is the engine's, read off
the records as the global group's pages over that sum would be too
coarse, so it is the program's default, 16). A windowed group that
gave nothing back would read 100% and more (it takes a chunk's pages
ahead); what it gives back behind the windows is the rest. Nothing to
read where the program records no such field.
"""

import inspect

from benchmarks import tick_records


def read(ctx):
    from mpi_cuda_cnn_tpu.serve.engine import PagedEngine

    page = inspect.signature(PagedEngine.__init__).parameters[
        "page_size"].default
    ticks = ctx["ticks"]
    shares = []
    # Depth AFTER a record is what the walk shows before the next one:
    # an empty record closes the list.
    walk = tick_records.walk(ticks + [dict.fromkeys(
        ("prefill", "decoded", "finished", "preempted", "aborted"), ())])
    next(walk, None)
    for t in ticks:
        after = next(walk)[2]
        if "pages_held" not in t:
            continue
        pinned = sum(-(-d // page) for d in after.values())
        if pinned:
            shares.append(t["pages_held"][1] / pinned)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
