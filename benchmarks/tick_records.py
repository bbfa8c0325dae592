"""The engine's per-iteration records (`tick_sink`), walked once for
the readers that need to know how deep each request's cache is."""

from __future__ import annotations


def walk(ticks):
    """Yields (index, record, depth) per iteration, where depth maps
    rid -> tokens in its cache BEFORE this iteration's prefill chunk
    and decode tick; requests that finished, were cut or preempted have
    left it. The caller may read the record's `prefill` ([slot, rid,
    n, ...] or empty) and `decoded` ([slot, rid] each) against it."""
    depth: dict[int, int] = {}
    for i, t in enumerate(ticks):
        yield i, t, depth
        if t["prefill"]:
            _, rid, n = t["prefill"][:3]
            depth[rid] = depth.get(rid, 0) + n
        for _, rid in t["decoded"]:
            depth[rid] += 1
        gone = list(t["finished"]) + list(t["preempted"]) + [
            rid for rid, _ in t["aborted"]]
        for rid in gone:
            depth.pop(rid, None)   # a preempted request prefills anew
