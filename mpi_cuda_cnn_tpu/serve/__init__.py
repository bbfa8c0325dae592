"""Serving engine: paged KV cache + iteration-level continuous batching.

The decode-side counterpart of the scanned-epoch training design — see
paged_cache.py (the memory layout), scheduler.py (the admission /
preemption policy), core.py (the ONE serving iteration, jax-free, that
engine.run and the fleet's replicas both drive), engine.py (the jitted
ticks and the single-engine driver), bench.py (the
`mctpu serve-bench` / `mctpu fleet-bench` harnesses), router.py (the
fleet's dispatch/health/fencing policy), fleet.py (N replicas behind
the router, failure-aware re-dispatch — ISSUE 7), prefix_cache.py (the
prefix-sharing tree: refcounted read-only pages, copy-on-write, LRU
retention — ISSUE 9; scheduler.py's SLOScheduler is the matching
SLO-aware admission/preemption policy), handoff.py (the disaggregated
prefill/decode pools' crash-safe page-granular KV transfer protocol —
ISSUE 13; fleet.py drives it, engine.adopt_pages is the device copy),
spec.py (batched speculative decoding's jax-free policy half —
ISSUE 14: prompt-lookup proposal, the greedy acceptance law, the round
scaffold the serving iteration runs; the engine compiles the batched
verify block, the scheduler owns the acceptance-aware page accounting).
"""

from .engine import PagedEngine, ServeResult
from .fleet import (
    EngineCompute,
    Fleet,
    FleetResult,
    Replica,
    SimCompute,
)
from .handoff import Handoff, parse_pools
from .paged_cache import PagedKVCache, PagePool, init_paged_cache
from .prefix_cache import PrefixCache
from .router import Router
from .scheduler import (
    ContinuousScheduler,
    Request,
    SLOPolicy,
    SLOScheduler,
    StaticScheduler,
    pages_for,
)
from .spec import LookupProposer, accept_len, lookup_propose

__all__ = [
    "ContinuousScheduler",
    "EngineCompute",
    "Fleet",
    "FleetResult",
    "Handoff",
    "LookupProposer",
    "PagedEngine",
    "PagedKVCache",
    "PagePool",
    "PrefixCache",
    "Replica",
    "Request",
    "Router",
    "SLOPolicy",
    "SLOScheduler",
    "ServeResult",
    "SimCompute",
    "StaticScheduler",
    "accept_len",
    "init_paged_cache",
    "lookup_propose",
    "pages_for",
    "parse_pools",
]
