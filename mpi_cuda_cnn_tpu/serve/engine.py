"""The serving engine: jitted paged ticks driven by a scheduler.

Exactly TWO compiled programs serve every request mix, so continuous
batching never retraces as the batch composition churns:

- `decode tick` — all engine slots advance one token in one forward
  (B = slots, k = 1, per-slot positions); dead/padded slots ride along
  with valid=False, their writes routed to the scratch page and their
  sampled tokens ignored by the host.
- `prefill chunk` — one slot advances `prefill_chunk` prompt tokens
  (B = 1, k = chunk, padded to the static chunk width). The LAST chunk
  of a prompt also yields the request's first generated token (argmax
  of the final valid position's logits) — TTFT is paid at prefill
  completion, not at the next decode tick.

A speculative engine (ISSUE 14: spec="lookup"/"draft") compiles ONE
additional program, the batched verify block — every slot's k candidate
rows at per-slot positions through the same paged_forward, rows past a
slot's round width riding along valid=False. serve/spec.py owns the
jax-free policy half (proposal, greedy acceptance, the round
scaffold); the scheduler owns the acceptance-aware page accounting
(opportunistic growth toward k, rejected-draft page rollback at commit).

Both donate the page pools, so the cache updates in place across ticks
(utils/donation discipline; the pool is the engine's dominant buffer).
Sampling is greedy — the serving benches measure schedule/memory
effects, and greedy keeps static-vs-continuous token streams bitwise
comparable per request.

The host loop (`run`) is a driver: each pass it fires faults, runs ONE
serving iteration — serve/core.py's ServeCore, the body the fleet's
replicas step too: sweep deadlines/cancellations -> admit -> enforce
the queue bound -> at most one prefill chunk -> one decode tick over
every decoding slot — then idles or raises, watches the iteration's
time, and records it. Interleaving the single chunk between ticks
bounds how long a long prompt can stall token emission for in-flight
sequences (the Orca iteration-level property);
`decode_ticks`/`prefill_chunks` counts are the deterministic cost model
the CPU tests compare schedulers on.

Failure-awareness (ISSUE 4): `run` accepts a faults.FaultInjector whose
"serve.tick" site can squeeze the page pool (steal pages for a window
of ticks) or stall a tick; a tick watchdog counts iterations slower
than `watchdog_s`; every abort, rejection, expiry, injected fault, and
watchdog breach lands in `ServeResult.events` (obs `fault` records —
serve/bench.py writes them to the JSONL sink). Every submitted request
leaves with a terminal status; aborted slots return their pages through
the ownership-checked PagePool.free, and the pool invariant is checked
every iteration (over what changed since the last check) and whole at
the run's end.
"""

from __future__ import annotations

import dataclasses
import functools
import time
import zlib

import jax.numpy as jnp
import numpy as np

from ..models.transformer import TransformerLM
from ..obs.trace import PhaseSpans, part
from ..utils.donation import donate_jit
from .core import EngineCompute, ServeCore, build_scheduler, observe_tick
from .host_tier import TIER_SPILL_SITE
from .paged_cache import (
    PagedKVCache,
    PagePool,
    SlotStates,
    init_paged_cache,
    init_slot_states,
    paged_forward,
    pages_for,
)
from .pool import window_pages_per_slot
from .prefix_cache import empty_prefix_fields
from .spec import SPEC_MODES, LookupProposer, empty_spec_fields
from .scheduler import Request, SLOPolicy, tenant_block, terminal_fields


# The tick record's names for PagedKVCache.counts: token-expert pairs
# the held experts computed and held experts with at least one token
# (summed over the expert layers) and the largest load of one expert,
# where the model has expert layers; then the cache rows the read
# touched (summed over the layers), under the name of the pool's
# layout: latent rows, or K/V rows.
TICK_COUNTS = ("moe_assignments", "moe_experts_hit", "moe_load_max",
               "latent_rows_read", "kv_rows_read")
# ... and after them, where the model has windowed layers, the rows
# THEIR reads touched (`kv_rows_read` stays all layers).
WINDOW_COUNT = "kv_rows_read_window"
# ... and last, where the model's softmax layers select the blocks they
# read or it has linear layers: the compressed keys the selection
# scored (K/V heads and layers summed), the compressed keys its gathers
# moved (layers summed: the K/V heads share a gathered row), the blocks
# it chose (as the first), and the recurrent states written (decoding
# slots x linear layers).
SELECT_COUNTS = ("index_rows_read", "index_rows_gathered",
                 "sparse_blocks_selected", "state_slots_updated")
# The same vector as the prefill CHUNK's program returned it, on the
# record of an iteration that ran a chunk of a model with expert
# layers: its first two elements, the pairs the held experts computed
# for the chunk's rows and the held experts with at least one (summed
# over the expert layers) — what the chunk's grouped products stream.
CHUNK_COUNTS = ("chunk_moe_assignments", "chunk_moe_experts_hit")

# The key order of run()'s tick record (`spans` closes it): the core's
# shared fields and run()'s own, laid out as the trail's readers and the
# checked-in samples have them.
TICK_LAYOUT = (
    "tick", "now", "mode", "queue", "running", "prefilling", "free_pages",
    "backlog", "arrived", "admitted", "prefill", "decoded", "finished",
    "aborted", "preempted", "blocked", "preempted_for", "terminal",
    "state_crc", "compiled", "checked", *TICK_COUNTS, WINDOW_COUNT,
    *CHUNK_COUNTS, *SELECT_COUNTS, "pages_held", "state_resets",
    "window_pages_freed", "squeezed", "spec",
    "prefix_hits", "prefix", "prefix_readmits",
)


def request_record(r: Request, mode: str) -> dict:
    """One request as an obs `request` field dict — THE record shape
    report/trace consume, shared by ServeResult and FleetResult so the
    two surfaces cannot drift. Aborted requests carry null latencies
    where the moment never happened (no first token -> ttft_ms null);
    queue_wait_ms anchors on FIRST admission, null if never admitted."""
    return {
        "id": r.rid,
        "mode": mode,
        "status": r.status,
        "tenant": r.tenant or "default",
        "prompt_tokens": int(r.prompt.size),
        # The token budget (ISSUE 15): what obs/replay.py needs to
        # reconstruct static reservations and done-checks from the
        # trail alone (output_tokens only equals it for finished
        # requests).
        "max_new_tokens": int(r.max_new_tokens),
        "output_tokens": len(r.out),
        "ttft_ms": (None if r.first_token_at is None
                    else round(1e3 * (r.first_token_at - r.arrival), 3)),
        "latency_ms": (None if r.finished_at is None
                       else round(1e3 * (r.finished_at - r.arrival), 3)),
        # Lifecycle anchors (ISSUE 6): absolute arrival on the run's
        # clock (pairs with tick records' "now").
        "arrival_s": round(r.arrival, 4),
        "queue_wait_ms": (None if r.admitted_at is None
                          else round(1e3 * (r.admitted_at - r.arrival), 3)),
        # Quota skip-over share of the queue wait (ISSUE 11): time an
        # SLOScheduler spent skipping this request over for its own
        # tenant's quota — zero under FCFS/capacity waits.
        "queue_wait_quota_ms": round(1e3 * r.quota_wait_s, 3),
        "preemptions": r.preemptions,
        **({"reason": r.fail_reason} if r.fail_reason else {}),
    }


@dataclasses.dataclass
class ServeResult:
    """One engine run: every submitted request in a terminal status
    (with its timestamps filled in) plus the aggregate counters the
    bench reports. `requests` includes aborted ones — filter by
    `status` or use `finished_requests`."""

    mode: str
    requests: list[Request]
    decode_ticks: int
    prefill_chunks: int
    preemptions: int
    duration_s: float
    events: list[dict] = dataclasses.field(default_factory=list)
    watchdog_slow_ticks: int = 0
    # Prefix-cache structural counters (ISSUE 9): always present (zeros
    # with sharing off) so gated metrics exist in every run.
    prefix: dict = dataclasses.field(default_factory=empty_prefix_fields)
    # Speculative-decoding counters (ISSUE 14): rounds run, draft
    # tokens proposed, draft tokens accepted — always present (zeros
    # with spec off) so the gated metrics exist in every run.
    spec: dict = dataclasses.field(default_factory=empty_spec_fields)
    # Flight-recorder chain (ISSUE 15): crc32 chained over every tick's
    # state digest — ONE number that pins the full per-tick state
    # trajectory, stamped in the summary so the 0%/equal determinism
    # gates cover it even on summary-only runs.
    state_crc: int = 0

    @property
    def finished_requests(self) -> list[Request]:
        return [r for r in self.requests if r.status == "finished"]

    @property
    def output_tokens(self) -> int:
        # Tokens emitted before an abort were still served.
        return sum(len(r.out) for r in self.requests)

    @property
    def tokens_per_s(self) -> float:
        return self.output_tokens / max(self.duration_s, 1e-9)

    def status_counts(self) -> dict[str, int]:
        counts: dict[str, int] = {}
        for r in self.requests:
            counts[r.status] = counts.get(r.status, 0) + 1
        return counts

    def ttft_ms(self) -> list[float]:
        return [1e3 * (r.first_token_at - r.arrival)
                for r in self.finished_requests]

    def tpot_ms(self) -> list[float]:
        """Per-output-token latency (time-per-output-token) after the
        first token, per finished request; requests with one token
        report 0."""
        return [
            1e3 * (r.finished_at - r.first_token_at) / max(len(r.out) - 1, 1)
            for r in self.finished_requests
        ]

    def request_records(self) -> list[dict]:
        """Per-request field dicts in the obs `request` event shape
        (the caller stamps them through MetricsLogger/make_record).
        Aborted requests carry null latencies where the moment never
        happened (no first token -> ttft_ms null)."""
        return [request_record(r, self.mode)
                for r in sorted(self.requests, key=lambda r: r.rid)]

    def summary(self) -> dict:
        # Nearest-rank percentiles (obs.metrics.pct_nearest) — the ONE
        # serving convention, so `mctpu report`'s per-request table and
        # this summary can never disagree on the same run.
        from ..obs.metrics import pct_nearest

        ttft, tpot = self.ttft_ms(), self.tpot_ms()
        return {
            "mode": self.mode,
            "requests": len(self.requests),
            "statuses": self.status_counts(),
            "output_tokens": self.output_tokens,
            "decode_ticks": self.decode_ticks,
            "prefill_chunks": self.prefill_chunks,
            "preemptions": self.preemptions,
            "watchdog_slow_ticks": self.watchdog_slow_ticks,
            "duration_s": round(self.duration_s, 4),
            "tokens_per_s": round(self.tokens_per_s, 2),
            # Per-tick state-digest chain (ISSUE 15): gated at 0%/equal
            # by the determinism gates like trace_crc/blame_crc.
            "state_crc": self.state_crc,
            "ttft_p50_ms": pct_nearest(ttft, 50),
            "ttft_p99_ms": pct_nearest(ttft, 99),
            "tpot_p50_ms": pct_nearest(tpot, 50),
            "tpot_p99_ms": pct_nearest(tpot, 99),
            # Prefix-sharing counters (ISSUE 9), flat so `mctpu
            # compare` gates them as serve.<mode>.prefix_hits etc.
            **self.prefix,
            # Speculative-decoding counters (ISSUE 14), flat so `mctpu
            # compare` gates them as serve.<mode>.spec_rounds etc.
            **self.spec,
            # Per-tenant status/latency counts (ISSUE 8): the summary
            # keys `mctpu compare` flattens as serve.<mode>.tenant.<t>.*
            # and `mctpu health` falls back to on summary-only logs.
            "tenants": tenant_block(self.requests),
        }


def _observe_request(registry, r: Request) -> None:
    """Fold one terminal request into the registry: a per-status
    counter plus the latency histograms (same formulas as
    ServeResult.ttft_ms/tpot_ms, so the registry's percentiles and the
    summary's can never disagree on the same run). Null moments —
    aborted before admission or before the first token — are skipped,
    the serving null convention. A TAGGED tenant (ISSUE 8) additionally
    lands in `serve.tenant.<name>.*` twins of every metric, which is
    what `mctpu health` reads off a summary-only run; untagged requests
    stay global-only (a single-tenant run must not pay double)."""
    prefixes = ["serve."]
    if r.tenant is not None:
        prefixes.append(f"serve.tenant.{r.tenant}.")
    for p in prefixes:
        registry.inc(f"{p}requests_{r.status}")
        if r.admitted_at is not None:
            registry.observe(f"{p}queue_wait_ms",
                             1e3 * (r.admitted_at - r.arrival))
        if r.quota_wait_s > 0:
            # The SLOScheduler skip-over share of the wait (ISSUE 11),
            # split out so a quota-throttled tenant's policy wait can't
            # masquerade as a capacity shortage. Observed only when
            # nonzero: FCFS runs must not bury the histogram in zeros.
            registry.observe(f"{p}queue_wait_quota_ms",
                             1e3 * r.quota_wait_s)
        if r.status != "finished":
            continue
        registry.observe(f"{p}ttft_ms",
                         1e3 * (r.first_token_at - r.arrival))
        registry.observe(
            f"{p}tpot_ms",
            1e3 * (r.finished_at - r.first_token_at) / max(len(r.out) - 1, 1),
        )


def _observe_run_tick(registry, rec: dict, out, core: ServeCore) -> None:
    """Fold one run() tick record into the registry: the shared gauges
    and counters (core.observe_tick), then what only run() records."""
    observe_tick(registry, rec)
    registry.set("serve.prefilling_slots", rec["prefilling"])
    registry.set("serve.prefill_backlog", rec["backlog"])
    if out.emitted:
        registry.inc("serve.tokens_emitted", out.emitted)
    for name in (*TICK_COUNTS, WINDOW_COUNT, *CHUNK_COUNTS, *SELECT_COUNTS,
                 "state_resets"):
        if name in rec:
            registry.set(f"serve.{name}", rec[name])
    for _, _, accepted in out.spec or ():
        registry.observe("serve.spec.accepted", accepted)
    if core.prefix is not None:
        for key in ("cow", "evictions", "inserts"):
            if out.prefix_tick[key]:
                registry.inc(f"serve.prefix.{key}", out.prefix_tick[key])
        registry.set("serve.prefix.shared_pages", core.prefix.shared_pages)
        registry.set("serve.prefix.retained_pages",
                     rec["prefix"]["retained_pages"])
        if core.tier is not None:
            # Cumulative counters are SET, not inc'd: the tier already
            # accumulates; gauges mirror it.
            for key, val in core.tier.stats.items():
                registry.set(f"serve.tier.{key}", val)
            registry.set("serve.tier.host_used", core.tier.host_used)
    for r in out.new_fin + out.new_drop:
        _observe_request(registry, r)


def _fire_tick_faults(faults, pool: PagePool, squeezes: list[dict],
                      tick_idx: int, events: list[dict]) -> None:
    """The "serve.tick" fault site of iteration `tick_idx`: a `squeeze`
    steals pages into `squeezes` for a window of ticks, a `slow` stalls
    the tick; then the squeezes whose window has ended give their pages
    back."""
    for f in faults.fire("serve.tick", tick_idx):
        if f.kind == "squeeze":
            # Steal up to `pages` pages for `ticks` ticks —
            # ownership-checked like any sequence's, so the end-of-run
            # pool invariant still proves zero leaks with faults active.
            owner = f"_fault_squeeze_{tick_idx}"
            want = int(f.arg("pages", 1))
            got = pool.try_alloc(min(want, pool.free_pages), owner) or []
            squeezes.append({"pages": got, "owner": owner,
                             "until": tick_idx + int(f.arg("ticks", 1))})
        elif f.kind == "slow":
            faults.sleep(float(f.arg("s", 0.05)))
    events.extend(faults.drain_events())
    for sq in [s for s in squeezes if s["until"] <= tick_idx]:
        if sq["pages"]:
            pool.free(sq["pages"], sq["owner"])
        squeezes.remove(sq)


class DraftProposer:
    """Model-draft proposal behind the LookupProposer interface
    (ISSUE 14): a cheap draft model proposes each slot's k-1 candidate
    tokens by greedy argmax over a fixed sliding WINDOW of the
    request's committed context — cacheless, so the draft needs no
    paged pools, no COW, and no handoff story of its own (the full
    per-slot draft KV cache is the chip-scale follow-up; T=0 exactness
    never depends on the draft, only the acceptance rate does). The
    draft steps are BATCHED across slots like the verify block: one
    jitted (batch, W) window forward per draft position — k-1 forwards
    and k-1 host syncs per tick, however many slots speculate — with
    static shapes, compiled once."""

    def __init__(self, model: TransformerLM, params, *, window: int = 32,
                 batch: int = 1):
        import jax

        self.model = model
        self.window = min(window, model.max_seq)
        self.batch = batch
        self.params = params

        @jax.jit
        def step(params, toks, n_valid):
            # Full causal forward over the padded windows; each row's
            # proposal is the argmax after its last VALID position
            # (causal masking keeps the pad tail out of that logit).
            logits = model.apply(params, toks, moe_inference=True)
            picks = jnp.argmax(logits, axis=-1)            # (B, W)
            idx = jnp.maximum(n_valid - 1, 0)
            return jnp.take_along_axis(
                picks, idx[:, None], axis=1)[:, 0].astype(jnp.int32)

        self._step = step

    def propose(self, ctx: np.ndarray, n_props: int) -> np.ndarray:
        return self.propose_batch([ctx], [n_props])[0]

    def propose_batch(self, ctxs, n_props):
        """Per-slot proposals for one round, drafted in lockstep: draft
        position i runs ONE (batch, W) forward for every slot at once
        (rows past a slot's own width ride along; their picks are
        dropped host-side)."""
        n_max = max(n_props, default=0)
        if n_max == 0:
            return [np.empty(0, np.int32) for _ in ctxs]
        if len(ctxs) > self.batch:
            raise ValueError(
                f"{len(ctxs)} draft contexts exceed batch {self.batch}")
        w = self.window
        bufs = [[int(t) for t in c[-w:]] for c in ctxs]
        outs = [[] for _ in ctxs]
        for step_i in range(n_max):
            toks = np.zeros((self.batch, w), np.int32)
            n_valid = np.ones((self.batch,), np.int32)
            for i, buf in enumerate(bufs):
                win = buf[-w:]
                toks[i, : len(win)] = win
                n_valid[i] = max(len(win), 1)
            # The sanctioned sync: one host transfer per BATCHED draft
            # step (every slot's pick in one array), not per sequence.
            # mctpu: disable=MCT007
            picks = np.asarray(self._step(
                self.params, jnp.asarray(toks), jnp.asarray(n_valid)))
            for i, buf in enumerate(bufs):
                if step_i < n_props[i]:
                    # Host-side already (the batched fetch above);
                    # int() here is list bookkeeping, not a new sync.
                    # mctpu: disable=MCT007
                    t = int(picks[i])
                    outs[i].append(t)
                    buf.append(t)
        return [np.asarray(o, np.int32) for o in outs]


class PagedDraftProposer:
    """The paged draft-model KV cache (ISSUE 17, the PR-14 remainder):
    the draft becomes just another paged-cache client — its own small
    PagePool + per-slot block tables growing and rolling back in
    lockstep with the target's commit_spec — replacing the cacheless
    sliding-window draft that recomputes ~W x the FLOPs per round.

    Per round and slot the paged draft runs CATCH-UP (the tokens
    committed since its last round — at steady state the previous
    round's accepted count, not the whole window) plus n single-token
    proposal steps, against its own persistent KV pages. At round end
    each slot's draft rows are TRIMMED back to the committed context
    (pages holding only proposal rows return to the draft pool) — the
    rollback twin of the target scheduler's commit_spec page law, so a
    rejected draft token's KV is never live on either cache. Proposal
    rows inside the kept partial page are overwritten before they are
    ever read (paged_update_attend writes first; the causal mask keeps
    unwritten positions out of the softmax).

    Page accounting laws (what `mctpu replay` mirrors, the state_crc
    extension): after a slot's round the draft holds exactly
    pages_for(committed_rows) pages, where committed_rows is the
    slot's pre-commit `cached` (= len(prompt)+len(out)-1 at propose
    time); a slot's state persists LAZILY across release (reset on the
    next rid mismatch or context shrink), and the pool is sized to
    slots x pages_for(max_len) so the deterministic schedule never
    depends on a draft-pool dry path. T=0 exactness never depends on
    the draft (the acceptance scaffold is the same for any proposer) —
    only FLOPs per round do.
    """

    # run_round feeds slot identities (and every slot's real context)
    # to proposers that carry per-slot cache state.
    needs_slots = True

    def __init__(self, model: TransformerLM, params, *, slots: int,
                 page_size: int, max_len: int, cache_dtype=jnp.float32,
                 chunk: int = 32):
        self.model = model
        self.params = params
        self.slots = slots
        self.page_size = page_size
        self.max_len = min(max_len, model.max_seq)
        self.table_width = pages_for(self.max_len, page_size)
        self.chunk = chunk
        # +1 for the reserved scratch page: full per-slot coverage, so
        # draft paging changes FLOPs, never the serving schedule.
        self.pool = PagePool(slots * self.table_width + 1)
        tmpl = init_paged_cache(model, slots=slots,
                                num_pages=slots * self.table_width + 1,
                                page_size=page_size, dtype=cache_dtype,
                                max_len=self.max_len)
        self._pages = tmpl.pages
        # Per-slot draft state, indexed by ENGINE slot idx: the rid the
        # cache rows belong to, committed rows held, physical pages.
        self._rid: list = [None] * slots
        self._cached = [0] * slots
        self._spages: list[list[int]] = [[] for _ in range(slots)]

        ck = self.chunk

        def catchup(cache: PagedKVCache, params, toks, pos0, n_valid):
            positions = pos0[:, None] + jnp.arange(ck)[None, :]
            valid = jnp.arange(ck)[None, :] < n_valid[:, None]
            _, cache = paged_forward(model, params, toks, positions,
                                     valid, cache)
            return cache

        def step(cache: PagedKVCache, params, toks, pos, live):
            logits, cache = paged_forward(
                model, params, toks[:, None], pos[:, None], live[:, None],
                cache,
            )
            return cache, jnp.argmax(
                logits[:, 0, :], axis=-1).astype(jnp.int32)

        self._catchup = donate_jit(catchup)
        self._step = donate_jit(step)

    @property
    def tracked(self) -> int:
        """Slots carrying draft-cache state (the digest's lazy-state
        count — entries persist across slot release until reused)."""
        return sum(1 for r in self._rid if r is not None)

    def digest_state(self) -> tuple:
        """The draft pool's share of the per-tick state digest (ISSUE
        17): free draft pages + slots carrying lazy draft state —
        `mctpu replay` re-derives both from the spec round records (the
        pages_for page law)."""
        return (1, self.pool.free_pages, self.tracked)

    def _owner(self, idx: int) -> tuple:
        return ("draft", idx)

    def _reset(self, idx: int, rid) -> None:
        if self._spages[idx]:
            self.pool.free(self._spages[idx], self._owner(idx))
        self._rid[idx] = rid
        self._cached[idx] = 0
        self._spages[idx] = []

    def _ensure_pages(self, idx: int, rows: int) -> None:
        need = pages_for(rows, self.page_size) - len(self._spages[idx])
        if need > 0:
            got = self.pool.try_alloc(need, self._owner(idx))
            assert got is not None, "draft pool sized to full coverage"
            self._spages[idx].extend(got)

    def _trim(self, idx: int, rows: int) -> None:
        keep = pages_for(rows, self.page_size)
        extra = self._spages[idx][keep:]
        if extra:
            self.pool.free(extra, self._owner(idx))
            del self._spages[idx][keep:]

    def _cache_view(self, table: np.ndarray) -> PagedKVCache:
        return PagedKVCache(pages=self._pages,
                            block_table=jnp.asarray(table),
                            page_size=self.page_size)

    def end_run(self) -> None:
        """Release every slot's draft pages and prove the draft pool
        clean — the engine's end-of-run twin of the main pool check."""
        for idx in range(self.slots):
            if self._spages[idx]:
                self.pool.free(self._spages[idx], self._owner(idx))
            self._rid[idx] = None
            self._cached[idx] = 0
            self._spages[idx] = []
        self.pool.check()
        assert self.pool.free_pages == self.pool.usable, \
            "draft pages leaked"

    def propose_batch(self, ctxs, n_props, dslots):
        """One paged draft round over this tick's decoding slots:
        reset stale state (rid change / context shrink — the preempt
        rollback), grow each slot's block table to cover catch-up +
        proposal rows, run batched catch-up chunks then n single-token
        steps, and trim every slot back to its committed rows."""
        outs = [np.empty(0, np.int32) for _ in ctxs]
        work = []       # (idx, ctx, n, committed_rows)
        for s, ctx, n in zip(dslots, ctxs, n_props):
            idx = s.idx
            rows = len(ctx) - 1     # committed KV rows the draft holds
            if self._rid[idx] != s.req.rid or self._cached[idx] > rows:
                self._reset(idx, s.req.rid)
            self._ensure_pages(idx, rows + max(n, 0))
            work.append((idx, ctx, n, rows))
        # Batched catch-up: every behind slot advances `chunk` rows per
        # jitted call until all hold their committed rows.
        table = np.zeros((self.slots, self.table_width), np.int32)
        for idx, _, _, _ in work:
            table[idx, : len(self._spages[idx])] = self._spages[idx]
        while True:
            toks = np.zeros((self.slots, self.chunk), np.int32)
            pos0 = np.zeros((self.slots,), np.int32)
            n_valid = np.zeros((self.slots,), np.int32)
            behind = False
            for idx, ctx, _, rows in work:
                got = self._cached[idx]
                if got >= rows:
                    continue
                n = min(self.chunk, rows - got)
                toks[idx, :n] = ctx[got : got + n]
                pos0[idx] = got
                n_valid[idx] = n
                self._cached[idx] = got + n
                behind = True
            if not behind:
                break
            cache = self._catchup(
                self._cache_view(table), self.params, jnp.asarray(toks),
                jnp.asarray(pos0), jnp.asarray(n_valid),
            )
            self._pages = cache.pages
        # n proposal steps, batched across slots: step t feeds the
        # previous pick (step 1: the slot's last committed token) at
        # position rows + t - 1, writing that row and reading the
        # causal prefix below it.
        n_max = max(n_props, default=0)
        if n_max > 0:
            cur = np.zeros((self.slots,), np.int32)
            pos = np.zeros((self.slots,), np.int32)
            for idx, ctx, n, rows in work:
                cur[idx] = ctx[-1]
                pos[idx] = rows
            for t in range(n_max):
                live = np.zeros((self.slots,), bool)
                for i, (idx, ctx, n, rows) in enumerate(work):
                    live[idx] = t < n
                cache, picks = self._step(
                    self._cache_view(table), self.params,
                    jnp.asarray(cur), jnp.asarray(pos), jnp.asarray(live),
                )
                self._pages = cache.pages
                # The sanctioned sync: one host transfer per BATCHED
                # draft step (every slot's pick in one array).
                # mctpu: disable=MCT007
                picks = np.asarray(picks)
                for i, (idx, ctx, n, rows) in enumerate(work):
                    if t < n:
                        outs[i] = np.append(outs[i], picks[idx])
                        cur[idx] = picks[idx]
                        pos[idx] += 1
        # Roll back to committed rows: pages holding only proposal
        # rows return to the draft pool (commit_spec's rollback twin).
        for idx, ctx, n, rows in work:
            self._trim(idx, rows)
            self._cached[idx] = rows
        return [np.asarray(o, np.int32) for o in outs]


def _closes_spans(run):
    """PagedEngine.run, with its phase recorder taken off the engine
    and its open phase closed and its collection and compile hooks
    taken down however the run ends — a failed pool check, the idle
    RuntimeError and an injected fault leave mid-phase."""

    @functools.wraps(run)
    def wrapper(self, *args, **kwargs):
        try:
            return run(self, *args, **kwargs)
        finally:
            spans, self._spans = self._spans, None
            if spans is not None:
                spans.close()
                spans.unwatch()

    return wrapper


class PagedEngine:
    """Greedy serving engine over a paged KV cache.

    slots bounds the decode batch; num_pages * page_size tokens is the
    TOTAL cache budget shared by all in-flight sequences (page 0 is
    scratch); max_len bounds any one sequence (prompt + new tokens) and
    sizes the block table. cache_dtype composes with the shipped
    --decode-cache-dtype forms (float32 / bfloat16 / int8).

    The paged read is chosen by the code (paged_cache.bounded_read: by
    each slot's depth, its step from the bytes the table moves), in
    both jitted programs (run_prefill_chunk / run_decode_tick) alike.
    `weights_dtype` quantizes the decode GEMV weights ONCE
    at construction (ops/pallas_gemv.quantize_decode_params — int8
    per-channel absmax, bf16 cast, or f32 pass-through; "auto" routes
    via generate.pick_weights_dtype, the pick_cache_dtype twin).
    """

    def __init__(self, model: TransformerLM, params, *, slots: int = 4,
                 num_pages: int = 64, page_size: int = 16,
                 prefill_chunk: int = 32, cache_dtype="float32",
                 max_len: int | None = None,
                 weights_dtype: str = "float32", spec: str = "off",
                 spec_k: int = 8, spec_ngram: int = 2,
                 draft_model: TransformerLM | None = None,
                 draft_params=None, draft_cache: str = "window"):
        from ..models.generate import pick_cache_dtype, pick_weights_dtype
        from ..ops.pallas_gemv import quantize_decode_params

        if spec not in SPEC_MODES:
            raise ValueError(f"spec {spec!r}: want one of {SPEC_MODES}")
        if draft_cache not in ("window", "paged"):
            raise ValueError(
                f"draft_cache {draft_cache!r}: want 'window' or 'paged'")
        if spec != "off" and spec_k < 2:
            raise ValueError(
                f"spec_k must be >= 2 (k={spec_k} would propose nothing)")
        if spec == "draft":
            if draft_model is None or draft_params is None:
                raise ValueError(
                    "spec='draft' needs draft_model + draft_params")
            if draft_model.vocab != model.vocab:
                raise ValueError(
                    f"target vocab {model.vocab} != draft vocab "
                    f"{draft_model.vocab}")
        self.spec_mode = spec
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram
        self.draft_cache = draft_cache
        self.model = model
        self.slots = slots
        self.page_size = page_size
        self.num_pages = num_pages
        self.prefill_chunk = prefill_chunk
        self.weights_dtype = pick_weights_dtype(
            weights_dtype, heads=model.heads, kv_heads=model.n_kv)
        # One-time conversion: the hot loop only ever reads this form.
        self.params = quantize_decode_params(params, self.weights_dtype)
        # Chooses nothing: read by benchmarks/compile_only.py, and goes
        # with that line (PagedKVCache.kernel).
        self.attn_kernel = "gather"
        if isinstance(cache_dtype, str) and cache_dtype == "auto":
            # VERDICT item 7: route the storage dtype from the banked
            # measurements — int8 for GQA/MQA, bfloat16 for MHA.
            cache_dtype = pick_cache_dtype("auto", heads=model.heads,
                                           kv_heads=model.n_kv)
        self.cache_dtype = jnp.dtype(cache_dtype)
        self.max_len = min(max_len or model.max_seq, model.max_seq)
        # Layer groups (TransformerLM.cache_groups): every model has
        # the global one, `num_pages` is its pool and admission's. A
        # model with windowed layers too has a second, `_window` =
        # (window, chunk), sized here to full coverage
        # (pool.WindowGroup); `_pages` is then one list of pools a
        # group, and a cache view one PagedKVCache a group.
        windows = [w for w, _ in model.cache_groups()]
        if windows[0] != 0 or len(windows) > 2:
            raise ValueError(
                f"layer groups with windows {windows}: the engine admits "
                "by a global group's pool and serves one windowed group "
                "beside it")
        self._window = (windows[1], prefill_chunk) if windows[1:] else None
        if self._window is not None and spec != "off":
            raise ValueError(
                "speculation rolls back rejected rows' pages in ONE "
                "layer group; a windowed group may already have given "
                "back the pages a rollback would need")
        # The page-less group (TransformerLM.state_layers): one state a
        # slot a linear layer, `_states`; a slot's row of each is the
        # slot's own, so there is nothing to allocate or to free.
        self._states = init_slot_states(model, slots) or None
        if self._states is not None and spec != "off":
            raise ValueError(
                "speculation takes rejected rows back by freeing their "
                "pages; a linear layer's state has already absorbed them "
                "and cannot be rolled back")
        tmpl = init_paged_cache(
            model, slots=slots, num_pages=num_pages, page_size=page_size,
            dtype=self.cache_dtype, max_len=self.max_len,
            window_pages=None if self._window is None else 1 + slots
            * window_pages_per_slot(*self._window, page_size, self.max_len))
        if self._window is None:
            self._pages = tmpl.pages
            self._table_width = tmpl.block_table.shape[1]
        else:
            self._pages = tuple(c.pages for c in tmpl)
            self._table_width = tmpl[0].block_table.shape[1]

        def tick(cache: PagedKVCache, params, toks, pos, live):
            logits, cache = paged_forward(
                model, params, toks[:, None], pos[:, None], live[:, None],
                cache,
            )
            return cache, jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)

        chunk = prefill_chunk

        def prefill(cache: PagedKVCache, params, toks, pos0, n_valid):
            positions = pos0 + jnp.arange(chunk)[None, :]
            valid = (jnp.arange(chunk) < n_valid)[None, :]
            logits, cache = paged_forward(
                model, params, toks, positions, valid, cache
            )
            nxt = jnp.argmax(logits[0, jnp.maximum(n_valid - 1, 0)])
            return cache, nxt.astype(jnp.int32)

        def copy(pages, src, dst):
            # Copy-on-write (ISSUE 9): duplicate one physical page's
            # rows across every layer's pools — the divergent request
            # writes into the copy, the shared source stays read-only.
            return [
                {name: c[name].at[dst].set(c[name][src]) for name in c}
                for c in pages
            ]

        def adopt(pages, src_pool, src, dst):
            # Cross-engine KV transfer (ISSUE 13): scatter the sender
            # pool's page rows (keys, values, int8 scales alike) into
            # this engine's pools at the destination indices — the
            # device half of the prefill->decode handoff.
            return [
                {name: c[name].at[dst].set(s[name][src]) for name in c}
                for c, s in zip(pages, src_pool)
            ]

        def restore(pages, host_rows, dst):
            # Host-tier readmission (ISSUE 17): scatter one spilled
            # page's host-resident rows back into every layer's pools
            # at the freshly allocated device page — adopt()'s
            # host->device twin, one page per call (readmissions are
            # per-walk-chunk events, not bulk transfers).
            return [
                {name: c[name].at[dst].set(h[name]) for name in c}
                for c, h in zip(pages, host_rows)
            ]

        # Donate the cache: the page pools update in place tick-to-tick
        # (the engine always adopts the returned cache) instead of
        # allocating a second pool-sized buffer per dispatch. donate_jit
        # is the repo's ONE donation spelling (`mctpu lint` MCT003).
        self._tick = donate_jit(tick)
        self._prefill = donate_jit(prefill)
        self._copy = donate_jit(copy)
        self._adopt = donate_jit(adopt)
        self._restore = donate_jit(restore)
        # Speculative verify (ISSUE 14): ONE batched block forward per
        # round — every slot's k candidate rows at per-slot positions
        # through the same paged_forward the plain tick compiles, with
        # per-row validity (short rounds and dead slots write scratch).
        # Compiled only when speculation is configured: a spec-off
        # engine keeps exactly its two programs.
        self._spec = None
        self._draft_proposer = None
        # The phase recorder (obs.trace.PhaseSpans) of the driver this
        # engine serves, told here where a dispatch and the wait for
        # its tokens begin: run()'s for the length of a run that
        # records spans, None otherwise.
        self._spans = None
        # The last decode tick's counters (PagedKVCache.counts), still
        # on the device: run() fetches them inside `record`, and only
        # there. Beside them the last prefill chunk's.
        self._tick_counts = None
        self._chunk_counts = None
        if spec != "off":
            kk = spec_k

            def spec_tick(cache: PagedKVCache, params, toks, pos, valid):
                positions = pos[:, None] + jnp.arange(kk)[None, :]
                logits, cache = paged_forward(
                    model, params, toks, positions, valid, cache
                )
                return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)

            self._spec = donate_jit(spec_tick)
            if spec == "draft":
                dparams = quantize_decode_params(draft_params,
                                                 self.weights_dtype)
                if draft_cache == "paged":
                    self._draft_proposer = PagedDraftProposer(
                        draft_model, dparams, slots=slots,
                        page_size=page_size, max_len=self.max_len,
                        cache_dtype=self.cache_dtype,
                        chunk=prefill_chunk)
                else:
                    self._draft_proposer = DraftProposer(
                        draft_model, dparams, batch=slots)

    # -- host-side helpers ------------------------------------------------

    def _cache_view(self, table, rows=()):
        """The device cache under `table`: a (rows, table width) block
        table and one PagedKVCache, or for a model with a windowed
        group the two groups' tables and one PagedKVCache each; for a
        model with linear layers a tuple of that PagedKVCache and the
        slots' states, `rows` naming each batch row's slot."""
        if self._window is None:
            view = PagedKVCache(pages=self._pages,
                                block_table=jnp.asarray(table),
                                page_size=self.page_size)
            if self._states is None:
                return view
            return view, SlotStates(
                states=self._states, rows=jnp.asarray(rows, jnp.int32))
        return tuple(
            PagedKVCache(pages=pages, block_table=jnp.asarray(t),
                         page_size=self.page_size, window=window)
            for pages, t, window in zip(self._pages, table,
                                        (0, self._window[0])))

    def _tables(self, rows: int, slots):
        """Block tables of `rows` rows for `slots` ((row, slot) pairs):
        the global group's, and the windowed group's beside it where
        the model has one (`_cache_view`'s argument)."""
        table = np.zeros((rows, self._table_width), np.int32)
        for i, s in slots:
            table[i, : len(s.pages)] = s.pages
        if self._window is None:
            return table
        wtable = np.zeros((rows, self._table_width), np.int32)
        for i, s in slots:
            wtable[i, : len(s.wpages)] = s.wpages
        return table, wtable

    def _keep(self, cache):
        """Adopt a program's returned cache; returns its counts."""
        if self._states is not None:
            cache, store = cache
            self._states = store.states
        if self._window is None:
            self._pages = cache.pages
            return cache.counts
        self._pages = tuple(c.pages for c in cache)
        return cache[0].counts

    def _one_group(self, what: str) -> None:
        if self._window is not None:
            raise ValueError(
                f"{what} moves the pages of ONE layer group; this model "
                "has a windowed group beside the global one")
        if self._states is not None:
            raise ValueError(
                f"{what} moves a slot as its pages; this model's linear "
                "layers keep a state a slot that is no page and would "
                "stay behind")

    def compiled_programs(self) -> int:
        """Compiled forms held by the engine's jitted programs, summed
        (the tick record's `compiled`): it steps up when a dispatch
        meets a shape or dtype it has not seen and compiles."""
        programs = [self._tick, self._prefill, self._copy, self._adopt,
                    self._restore]
        if self._spec is not None:
            programs.append(self._spec)
        draft = self._draft_proposer
        if draft is not None:
            programs.append(draft._step)
            if isinstance(draft, PagedDraftProposer):
                programs.append(draft._catchup)
        return sum(p._cache_size() for p in programs)

    def copy_page(self, src: int, dst: int) -> None:
        """Device-side COW: duplicate page `src` into page `dst` in
        every layer's pools (keys and values, plus int8 scales). The
        caller (ServeCore.work) releases the shared source's reference
        via scheduler.cow_complete afterwards."""
        self._one_group("copy-on-write")
        self._pages = self._copy(self._pages, jnp.int32(src),
                                 jnp.int32(dst))

    def spill_page(self, page: int):
        """Fetch one device page's KV rows (every layer's keys/values,
        plus int8 scales) to host memory — HostTier.spill_fn. The
        device->host transfer happens HERE, before the pool frees the
        page; the page's content is then owned by the tier entry until
        readmission or host eviction."""
        self._one_group("spill")
        # Device->host fetch of the evicted page — the spill's one
        # sanctioned sync (an np.asarray per layer pool).
        # mctpu: disable=MCT007
        return [{name: np.asarray(c[name][page]) for name in c}
                for c in self._pages]

    def readmit_page(self, page: int, payload) -> None:
        """Restore a spilled page's host-resident KV rows into device
        page `page` — HostTier.readmit_fn, called only AFTER the CRC
        verify accepted the entry (a refused spill is never restored,
        so garbage rows cannot enter the pools)."""
        self._one_group("readmission")
        self._pages = self._restore(
            self._pages,
            [{name: jnp.asarray(h[name]) for name in h} for h in payload],
            jnp.int32(page),
        )

    def adopt_pages(self, src_engine: "PagedEngine", src_pages,
                    dst_pages) -> None:
        """Adopt KV page content from another engine's pools (the
        disaggregated prefill->decode handoff, ISSUE 13): the sender's
        rows at `src_pages` land at this engine's `dst_pages`, every
        layer's keys/values (and int8 scales) together. Both engines
        must share the cache geometry — the fleet builds every replica
        from one model/config, which is also what makes the handed-off
        decode bitwise-equal to the unified one."""
        self._one_group("hand-off")
        src_engine._one_group("hand-off")
        if (src_engine.page_size != self.page_size
                or src_engine.cache_dtype != self.cache_dtype
                or len(src_engine._pages) != len(self._pages)):
            raise ValueError(
                "adopt_pages across mismatched cache geometries "
                f"(page_size {src_engine.page_size} vs {self.page_size}, "
                f"dtype {src_engine.cache_dtype} vs {self.cache_dtype})"
            )
        if len(src_pages) != len(dst_pages):
            raise ValueError(
                f"adopt_pages: {len(src_pages)} source pages vs "
                f"{len(dst_pages)} destinations"
            )
        # Pad the index arrays to the next power of two so the jitted
        # scatter compiles O(log num_pages) shapes, not one per handoff
        # page count. Pad entries copy the sender's scratch page onto
        # THIS pool's scratch page (page 0 on both ends) — scratch is
        # the sanctioned garbage sink, never read as live data.
        n = len(src_pages)
        width = 1 << max(n - 1, 0).bit_length()
        src = np.zeros(width, np.int32)
        dst = np.zeros(width, np.int32)
        src[:n] = src_pages
        dst[:n] = dst_pages
        self._pages = self._adopt(
            self._pages, src_engine._pages,
            jnp.asarray(src), jnp.asarray(dst),
        )

    def run_prefill_chunk(self, slot):
        """Advance `slot`'s prefill by one chunk on the device. Returns
        (rows written, next-token argmax of the chunk's last valid row
        — the request's first generated token iff this chunk completes
        the prefill). The token stays a device array so intermediate
        chunks pipeline under async dispatch: the caller converts it
        (int()) only on the COMPLETING chunk, where it is emitted.
        Scheduler bookkeeping (slot.cached, emission) is the caller's,
        the serving iteration (serve/core.py) reaching this through
        EngineCompute for run() and the fleet alike. Inside a run()
        that records spans, its recorder (already in `prefill.build`)
        is told where building the inputs ends and the dispatch
        begins, and times the block table and the puts as parts."""
        ctx = np.concatenate(
            [slot.req.prompt, np.asarray(slot.req.out, np.int32)]
        )
        n = min(self.prefill_chunk, slot.target - slot.cached)
        toks = np.zeros((1, self.prefill_chunk), np.int32)
        toks[0, :n] = ctx[slot.cached : slot.cached + n]
        with part(self._spans, "tables"):
            view = self._cache_view(self._tables(1, [(0, slot)]),
                                    [slot.idx])
        with part(self._spans, "puts"):
            inputs = (jnp.asarray(toks), jnp.int32(slot.cached),
                      jnp.int32(n))
        if self._spans is not None:
            self._spans.enter("prefill.dispatch")
        cache, nxt = self._prefill(view, self.params, *inputs)
        self._chunk_counts = self._keep(cache)
        return n, nxt

    def run_decode_tick(self, dslots) -> np.ndarray:
        """One batched decode tick over `dslots` (every other engine
        row rides along dead). Returns the per-row sampled tokens
        (index by slot.idx); cached/emit bookkeeping is the caller's.
        Inside a run() that records spans, its recorder (already in
        `tick.build`) is told where the dispatch and the wait for the
        tokens begin, and times its parts: the block tables, the puts,
        the copy of the ready tokens (`fetch`)."""
        toks = np.zeros((self.slots,), np.int32)
        pos = np.zeros((self.slots,), np.int32)
        live = np.zeros((self.slots,), bool)
        for s in dslots:
            toks[s.idx] = s.req.out[-1]
            pos[s.idx] = s.cached
            live[s.idx] = True
        with part(self._spans, "tables"):
            view = self._cache_view(
                self._tables(self.slots, [(s.idx, s) for s in dslots]),
                range(self.slots))
        with part(self._spans, "puts"):
            inputs = (jnp.asarray(toks), jnp.asarray(pos),
                      jnp.asarray(live))
        if self._spans is not None:
            self._spans.enter("tick.dispatch")
        cache, nxt = self._tick(view, self.params, *inputs)
        self._tick_counts = self._keep(cache)
        # The donated pools' old handles (a few per layer) go now, under
        # the device's work — not after the read below, where freeing
        # them is time the device stands idle (0.4 ms at 42 layers).
        del view, inputs
        if self._spans is not None:
            self._spans.enter("tick.wait")
            # The same read, its copy timed apart from the wait.
            return self._spans.fetch(nxt, np.asarray)
        # THE sanctioned sync: one host transfer per BATCHED tick
        # (every live slot's token in one array), not per sequence.
        # mctpu: disable=MCT007
        return np.asarray(nxt)

    def run_spec_tick(self, rounds):
        """ONE batched speculative verify over this tick's rounds
        (ISSUE 14): rounds is spec.run_round's [(slot, u, width)] —
        each slot's verify inputs land in its own engine row at its own
        positions [cached, cached+width), rows past a slot's width (and
        every dead slot) ride along valid=False with their writes
        routed to the scratch page. Returns each slot's per-row greedy
        picks (the verify_fn contract run_round consumes). Spans as in
        run_decode_tick."""
        kk = self.spec_k
        toks = np.zeros((self.slots, kk), np.int32)
        pos = np.zeros((self.slots,), np.int32)
        valid = np.zeros((self.slots, kk), bool)
        for s, u, w in rounds:
            toks[s.idx, :w] = u
            pos[s.idx] = s.cached
            valid[s.idx, :w] = True
        with part(self._spans, "tables"):
            view = self._cache_view(self._tables(
                self.slots, [(s.idx, s) for s, _, _ in rounds]))
        with part(self._spans, "puts"):
            inputs = (jnp.asarray(toks), jnp.asarray(pos),
                      jnp.asarray(valid))
        if self._spans is not None:
            self._spans.enter("tick.dispatch")
        cache, picks = self._spec(view, self.params, *inputs)
        self._tick_counts = self._keep(cache)
        del view, inputs    # as in run_decode_tick: before the read
        if self._spans is not None:
            self._spans.enter("tick.wait")
            picks = self._spans.fetch(picks, np.asarray)
        else:
            # The sanctioned sync: one host transfer per BATCHED verify
            # round (every slot's picks in one array), not per sequence.
            # mctpu: disable=MCT007
            picks = np.asarray(picks)
        return [picks[s.idx, :w] for s, _, w in rounds]

    def _tick_record(self, core: ServeCore, out, *, tick: int, now: float,
                     mode: str, arrived: list, squeezes: list[dict],
                     compiled: int) -> dict:
        """run()'s tick record (obs `tick` event shape) of a settled
        step, all but its `spans`: the core's shared fields and what
        only run() has, in TICK_LAYOUT's order."""
        sched = core.sched
        fields = core.tick_fields(out)
        fields.update({
            "tick": tick, "now": round(now, 4), "mode": mode,
            "queue": sum(1 for r in sched.queue if r.arrival <= now),
            "prefilling": sum(1 for s in sched.slots
                              if s.prefilling and not s.req.terminal),
            "backlog": sched.prefill_backlog(),
            "arrived": arrived,
            # Terminal detail (ISSUE 8): tenant + latency per request
            # reaching a terminal status THIS tick — the streaming
            # good/bad events the SLO burn-rate rules fold, emitted
            # when they happen instead of at end of run.
            "terminal": [terminal_fields(r)
                         for r in out.new_fin + out.new_drop],
            "compiled": compiled,
            # What the iteration's pool check verified: [pages, slots],
            # both pools summed (Scheduler.check_changed).
            "checked": list(sched.checked),
        })
        if out.decoded and self._tick_counts is not None:
            # What this tick's forward counted, all layers together
            # (paged_cache.paged_forward): one small array that left
            # the device beside the tokens, read here and nowhere else.
            # mctpu: disable=MCT007
            counted = np.asarray(self._tick_counts).tolist()
            if self.model.select is not None or self._states is not None:
                last = len(SELECT_COUNTS)
                fields.update(zip(SELECT_COUNTS, counted[-last:]))
                del counted[-last:]
            if self._window is not None:
                fields[WINDOW_COUNT] = counted.pop()
            names = (*TICK_COUNTS[:3],
                     "latent_rows_read" if self.model.attn is not None
                     else "kv_rows_read")
            fields.update(zip(names[-len(counted):], counted))
        if (out.prefill is not None and self._chunk_counts is not None
                and self.model.experts is not None):
            # ... and this iteration's chunk's, as its program counted
            # them: fetched here too, and nowhere else.
            # mctpu: disable=MCT007
            fields.update(zip(
                CHUNK_COUNTS, np.asarray(self._chunk_counts).tolist()))
        if squeezes:
            # Pages an injected squeeze currently holds: the replay
            # reconstruction needs it to account the pool's free count
            # (squeeze allocations have no scheduling event).
            fields["squeezed"] = sum(len(sq["pages"]) for sq in squeezes)
        if core.prefix is not None:
            # The `mctpu top` cache panel's LRU gauge, second in the
            # block: an O(tree) scan a storm's replicas do not pay.
            shared = fields["prefix"]
            fields["prefix"] = {
                "shared_pages": shared["shared_pages"],
                "retained_pages": core.prefix.retained_pages(), **shared}
        return {k: fields[k] for k in TICK_LAYOUT if k in fields}

    @_closes_spans
    def run(self, requests: list[Request], *, mode: str = "continuous",
            time_fn=time.perf_counter, faults=None, max_queue: int | None = None,
            watchdog_s: float = 0.0, sleep_fn=time.sleep,
            registry=None, tick_sink=None, prefix: bool = False,
            policy: SLOPolicy | None = None,
            spec: bool = False, host_pages: int = 0) -> ServeResult:
        """Serve `requests` to a terminal status each; return ServeResult.

        Requests are mutated in place (out/timestamps/status); arrivals
        and deadlines are seconds relative to run start on `time_fn`'s
        clock — the loop idles (sleep_fn) until the next arrival when
        there is nothing admitted to work on. `faults` injects
        squeeze/slow faults at the "serve.tick" site (tick value = the
        iteration index); watchdog_s > 0 counts iterations slower than
        that budget. Deterministic tests drive time_fn/sleep_fn with a
        faults.FakeClock.

        Observability (ISSUE 6): `registry` is an obs.MetricsRegistry
        the engine updates in place — per-tick gauges (queue depth,
        running/prefilling slots, free pages, chunked-prefill backlog)
        and per-request histograms (ttft_ms/tpot_ms/queue_wait_ms) —
        and `tick_sink` receives each per-iteration tick field dict as
        it happens (serve/bench.py points it at the metrics JSONL, which
        is what makes `mctpu top` live-tailable mid-run). Both default
        to off: the hot loop pays nothing unless asked.

        Phase spans (ISSUE 25): when either asked, every iteration's
        host time is split by what the host was doing — `schedule`
        (faults, sweep, admit, queue bound), `prefill.build` /
        `prefill.dispatch` / `prefill.wait` (the wait only on a
        completing chunk, whose token the host reads), `grow`,
        `tick.build` (under speculation: the proposal too) /
        `tick.dispatch` / `tick.wait`, `emit` (after either program),
        `idle` (the sleep), `bookkeep` (drains, state digest, pool
        check: paid in every run), `record` (this record's scans; it
        ends before the sink is called). They tile the iteration on
        the record's clock as `spans` ([phase, start, end], seconds
        since run start) and sit on the profiler's host track as
        `serve.iter/<phase>` (obs.trace.PhaseSpans); `now` and the
        watchdog's window are two of the same stamps. `compiled` counts
        the forms the engine's programs have compiled since the run
        began (before its first dispatch), so a compile inside a run
        shows as a step and a warmed run reads 0 throughout. Beside
        the spans (ISSUE 36): `parts`, the pieces of a phase timed
        where they run — `*.build/tables` (block tables and cache
        view), `*.build/puts` (the inputs' puts), `*.wait/fetch` (the
        copy of tokens already ready; the wait for them is the rest of
        the phase), `bookkeep/check` (the pool check, whose pages and
        slots verified are the record's `checked`); `gc_s`, the
        iteration's seconds of garbage collection by generation; and
        `stops`, its generation-2 collections and jax compiles.

        Prefix sharing + SLO policy (ISSUE 9): `prefix=True` puts a
        PrefixCache over the run's pool — a request whose prompt shares
        cached prefix pages prefills only its suffix (TTFT drops
        accordingly; outputs stay bitwise-identical in f32). `policy`
        upgrades continuous batching to the SLOScheduler (priority
        classes, per-tenant quotas, burn-driven preemption). Both apply
        to iteration-level scheduling only — static batching is the
        reservation baseline the comparison measures.

        Speculative decoding (ISSUE 14): `spec=True` (on an engine
        constructed with spec="lookup"/"draft") replaces the one-token
        decode tick with a speculative ROUND — per-slot k-token
        proposal, ONE batched verify forward, greedy acceptance
        committing 1..k tokens per slot per tick (serve/spec.py).
        Iteration-level only, like prefix sharing: static stays the
        one-token baseline. At T=0 (the engine's only sampling) the
        emitted streams are the target's own greedy continuations —
        bitwise-equal to a spec-off run per request, while the tick
        count drops with the acceptance rate.

        Host-tier spill (ISSUE 17): `host_pages > 0` (requires
        prefix=True) puts a bounded HostTier under the prefix cache —
        LRU-reclaimed refcount-0 prefix pages spill device->host
        instead of being discarded, and a later prefix hit readmits
        them host->device (serve/host_tier.py). CRC-sealed at the tier
        crossing: a torn/corrupt spill is refused and degrades to
        re-prefill. Outputs stay bitwise-identical to a spill-off run
        in f32; only the prefill-chunk count (and TTFT) change.
        """
        if spec and self.spec_mode == "off":
            raise ValueError(
                "run(spec=True) on an engine constructed with "
                "spec='off' — pass spec='lookup' or 'draft' at "
                "construction (the verify program compiles there)"
            )
        if spec and mode != "continuous":
            raise ValueError(
                "speculative decoding is iteration-level — continuous "
                "batching only (static is the one-token-per-tick "
                "reservation baseline)"
            )
        if host_pages == 0 and faults is not None:
            # Inert-fault contract, tier leg (mirrors Fleet.__init__):
            # without a host tier no spill ever happens, so a tier.spill
            # fault would silently never fire.
            inert = [f"{f.kind}@{f.site}"
                     for f in faults.pending(TIER_SPILL_SITE)]
            if inert:
                raise ValueError(
                    f"fault(s) {', '.join(sorted(set(inert)))} need a "
                    "host tier (--spill / host_pages > 0) — without one "
                    "they would silently never fire"
                )
        proposer = None
        if spec:
            proposer = (self._draft_proposer if self.spec_mode == "draft"
                        else LookupProposer(self.spec_ngram))
        sched = build_scheduler(
            slots=self.slots, num_pages=self.num_pages,
            page_size=self.page_size, max_len=self.max_len,
            max_queue=max_queue, prefix=prefix, policy=policy,
            host_pages=host_pages, mode=mode, spill_fn=self.spill_page,
            readmit_fn=self.readmit_page,
            tier_fault_poll=((lambda seq: faults.poll(TIER_SPILL_SITE, seq))
                             if faults is not None else None),
            window=self._window, states=self._states is not None,
        )
        core = ServeCore(EngineCompute(self), sched, proposer=proposer,
                         spec_k=self.spec_k)
        sched.submit(requests)
        n_reqs = sched.unfinished
        state_chain = 0
        events: list[dict] = []
        n_drop_seen = 0     # sched.dropped (append-only) read so far
        watchdog_slow = 0
        squeezes: list[dict] = []  # {"pages": [...], "until": tick}
        tick_idx = 0
        want_ticks = registry is not None or tick_sink is not None
        # Arrival announcements (ISSUE 11): each tick record names the
        # rids whose arrival fell due since the last one, so `mctpu
        # explain` can anchor every request's blame span on the tick
        # axis without needing the end-of-run request records.
        arrivals = sorted((r.arrival, r.rid) for r in requests)
        arr_cursor = 0
        t0 = time_fn()
        # Stamps below are seconds since t0. The recorder is told the
        # ones the loop reads anyway and reads the clock itself at the
        # other phase boundaries; without a consumer there is none.
        core.clock = lambda: time_fn() - t0
        spans = core.spans = self._spans = (
            PhaseSpans("serve.iter", time_fn, t0) if want_ticks else None)
        if spans is not None:
            spans.watch("serve")
        compiled0 = self.compiled_programs() if want_ticks else 0
        while sched.unfinished:
            iter_t0 = time_fn() - t0
            if spans is not None:
                spans.begin(tick_idx, "schedule", iter_t0)
            if faults is not None:
                _fire_tick_faults(faults, sched.pool, squeezes, tick_idx,
                                  events)
            sched_now = time_fn() - t0
            # The engine sweeps EVERY iteration: anyone holding a
            # Request may cancel it between two of them.
            out = core.work(sched_now, sweep=True)
            for r in out.swept:
                events.append({"kind": f"request_{r.status}", "id": r.rid,
                               "mode": mode, "t_rel": round(sched_now, 4)})
            for r in out.rejected:
                events.append({"kind": "request_rejected", "id": r.rid,
                               "mode": mode, "t_rel": round(sched_now, 4)})
            while n_drop_seen < len(sched.dropped):
                # admit/grow_for_decode may have failed a livelocked
                # request.
                r = sched.dropped[n_drop_seen]
                n_drop_seen += 1
                if r.status == "failed":
                    events.append({"kind": "request_failed", "id": r.rid,
                                   "mode": mode, "reason": r.fail_reason})

            # Watchdog window closes HERE: the idle branch below sleeps
            # on purpose (waiting for the next arrival / a squeeze to
            # lift), and counting that wait would turn every sparse
            # workload into a stream of false slow-tick alarms.
            now = time_fn() - t0
            busy_s = now - iter_t0

            if not out.progressed and sched.unfinished:
                if spans is not None:
                    spans.enter("idle", now)
                nxt_arrival = sched.next_arrival()
                # What is due is judged by the stamp admit() judged it
                # by: a request falling due after that read has not
                # been refused, it has not been looked at yet.
                if squeezes:
                    # An injected squeeze holds the pages the next step
                    # needs (admission or decode growth): idle one tick
                    # until the squeeze lifts.
                    sleep_fn(0.001)
                elif nxt_arrival is None:
                    raise RuntimeError("scheduler stalled with no queue")
                elif nxt_arrival <= sched_now:
                    raise RuntimeError(
                        f"request {sched.queue[0].rid} cannot be "
                        f"admitted into an idle engine — page pool "
                        f"({self.num_pages} pages of {self.page_size})"
                        " too small"
                    )
                else:
                    sleep_fn(min(nxt_arrival - sched_now, 0.05))
                if spans is not None:
                    spans.enter("bookkeep")
            elif spans is not None:
                spans.enter("bookkeep", now)
            if watchdog_s > 0 and busy_s > watchdog_s:
                watchdog_slow += 1
                if registry is not None:
                    registry.inc("serve.watchdog_slow_ticks")
                events.append({
                    "kind": "watchdog_slow_tick", "tick": tick_idx,
                    "mode": mode, "seconds": round(busy_s, 4),
                })
            # The drains and the state digest are paid on EVERY run
            # (bare runs included: the chain is what the determinism
            # gates pin on summary-only storms).
            core.settle(out)
            state_chain = zlib.crc32(out.state_crc.to_bytes(4, "little"),
                                     state_chain)
            # The engine checks the pool every iteration, over what
            # changed since the last check (the full scan is the run's
            # end's). The check is timed in `bookkeep`, but where
            # records were asked for its failure is raised only once
            # this iteration's record has reached the sink: the record
            # of the iteration that broke the pool is the one to have.
            check_failed = None
            try:
                with part(spans, "check"):
                    sched.check_changed()
            except AssertionError as e:
                if not want_ticks:
                    raise
                check_failed = e
            if not want_ticks:
                tick_idx += 1
                continue
            # The tick record (obs `tick` event shape), built only when
            # a telemetry consumer asked for it — the slot/queue scans
            # are the cost the docstring promises a bare run never
            # pays; the record itself is streamed, never retained (the
            # JSONL sink is the tick store — an in-memory list would
            # grow without bound on a long-lived serve).
            now = time_fn() - t0
            spans.enter("record", now)
            arrived_now = []
            while arr_cursor < len(arrivals) and \
                    arrivals[arr_cursor][0] <= now:
                arrived_now.append(arrivals[arr_cursor][1])
                arr_cursor += 1
            tick_rec = self._tick_record(
                core, out, tick=tick_idx, now=now, mode=mode,
                arrived=arrived_now, squeezes=squeezes,
                compiled=self.compiled_programs() - compiled0)
            # `record` ends here, before the sink: what a sink costs
            # (the profiler's start among them) lies between two
            # records' spans and inside none.
            tick_rec["spans"] = spans.end()
            tick_rec.update(spans.extras())
            if tick_sink is not None:
                tick_sink(tick_rec)
            if registry is not None:
                _observe_run_tick(registry, tick_rec, out, core)
            if check_failed is not None:
                raise check_failed
            tick_idx += 1

        # Release any squeeze that outlived the workload, evict every
        # retained prefix page (no slot holds a reference once all
        # requests are terminal), then prove the pool clean: zero
        # leaked, zero double-booked pages — with or without faults.
        for sq in squeezes:
            if sq["pages"]:
                sched.pool.free(sq["pages"], sq["owner"])
        prefix_fields = core.prefix_stats()
        if core.prefix is not None:
            core.prefix.clear()
            # clear() evicts; freeze the counters at pre-flush values
            # (end-of-run teardown is not cache pressure — and it never
            # SPILLS: a run-end spill burst would land after the last
            # tick's digest, leaving tier counters no record covers).
        if isinstance(proposer, PagedDraftProposer):
            # Release the draft pool and prove it clean — the draft's
            # twin of the main-pool leak check below.
            proposer.end_run()
        sched.check()
        terminal = sched.finished + sched.dropped
        if len(terminal) != n_reqs:
            raise RuntimeError(
                f"run lost requests: {len(terminal)} of {n_reqs} reached "
                "a terminal status"
            )
        assert sched.pool.free_pages == sched.pool.usable, "pages leaked"
        if sched.window is not None:
            wpool = sched.window.pool
            assert wpool.free_pages == wpool.usable, "windowed pages leaked"
        return ServeResult(
            mode=mode, requests=terminal, decode_ticks=core.decode_ticks,
            prefill_chunks=core.prefill_chunks,
            preemptions=sched.preemptions, duration_s=time_fn() - t0,
            events=events, watchdog_slow_ticks=watchdog_slow,
            prefix=prefix_fields, spec=core.spec_stats,
            state_crc=state_chain,
        )
