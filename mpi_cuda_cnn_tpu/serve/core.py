"""One serving iteration, for every driver (jax-free).

`ServeCore.step` is the iteration-level loop body of continuous
batching: sweep -> admit -> queue bound -> copy-on-write -> at most ONE
prefill chunk (its last one yields the request's first token) ->
grow_for_decode -> one decode tick or speculative round over every
decoding slot -> emit -> finish -> the static batch's drain; then the
bookkeeping: the drains of preempted / blocked / prefix events, the new
terminal tails and the state digest. `PagedEngine.run` drives it on a
wall clock for one engine; `fleet.Replica.step` drives it on the
fleet's stepped clock, N replicas interleaved.

What a driver owns stays out: fault plans, sleeping, the watchdog, the
sink, the registry. What differs between drivers goes in as what it is:

- the compute (`prefill_chunk` / `decode` / `verify` / `copy_page`:
  `EngineCompute` over a PagedEngine's jitted programs, or the fleet's
  device-free `SimCompute`);
- the scheduler (`build_scheduler`: FCFS, SLO-aware, or the static
  reservation baseline, which answers `release_at_once` / `batch_done`
  for itself);
- the proposer (speculation's draft source, or None);
- `clock`: the reader for moments inside a step. The engine passes its
  wall clock, so a first token is stamped after the wait for it; a
  driver on a stepped clock passes none and every moment of a step is
  the step's `now`;
- `spans`: an obs.trace.PhaseSpans the core tells each phase boundary
  (the driver opens the iteration in `schedule`; the compute tells the
  same recorder its dispatch boundaries) and whose `fetch` reads the
  completing chunk's token, or None;
- `on_emit` / `on_prefill_done`: the fleet's fenced commit and its
  prefill->decode handoff.

Two rules stay each driver's own, and are written there: WHEN to sweep
(`step`'s `sweep` argument — the engine every iteration, because anyone
holding a Request may cancel it; the fleet only when a deadline exists
or a cancel was flagged, because the O(queue) scan would dominate a
storm) and how often to check the pool, and in which form (the engine
`sched.check_changed()` every iteration and `sched.check()` at the
run's end; the fleet `sched.check()` every `check_every` steps).
"""

from __future__ import annotations

from .host_tier import HostTier
from .pool import PagePool, WindowGroup
from .prefix_cache import PrefixCache, empty_prefix_fields
from .scheduler import (
    ContinuousScheduler,
    Request,
    SLOScheduler,
    StaticScheduler,
    scheduler_digest,
)
from .spec import empty_spec_fields, run_round


class EngineCompute:
    """Model-backed compute: one PagedEngine (its own page pools);
    prefill / decode / verify are the engine's jitted programs through
    its run_prefill_chunk / run_decode_tick / run_spec_tick, looked up
    at call time (a test's fault wrapper replaces them on the class or
    the instance). The seam a test substitutes a fake behind."""

    def __init__(self, engine):
        self.engine = engine

    def prefill_chunk(self, slot) -> tuple[int, int]:
        return self.engine.run_prefill_chunk(slot)

    def decode(self, dslots):
        return self.engine.run_decode_tick(dslots)

    def copy_page(self, src: int, dst: int) -> None:
        self.engine.copy_page(src, dst)

    def adopt_pages(self, src_compute, src_pages, dst_pages) -> None:
        """Cross-engine KV page transfer (ISSUE 13): copy the sender
        engine's page rows into this engine's pools at the destination
        indices — the device half of the prefill->decode handoff."""
        self.engine.adopt_pages(src_compute.engine, src_pages, dst_pages)

    def verify(self, rounds):
        """Speculative verify (ISSUE 14): the batched verify program —
        the engine must have been constructed with spec="lookup" /
        "draft"."""
        return self.engine.run_spec_tick(rounds)


def build_scheduler(*, slots: int, num_pages: int, page_size: int,
                    max_len: int, max_queue: int | None = None,
                    prefix: bool = False, policy=None, host_pages: int = 0,
                    mode: str = "continuous", spill_fn=None,
                    readmit_fn=None, tier_fault_poll=None,
                    route_keys: set | None = None,
                    window: tuple[int, int] | None = None,
                    states: bool = False):
    """A fresh PagePool with the scheduler over it and, where asked,
    the prefix tree and the host tier under it (reached afterwards as
    `sched.pool`, `sched.prefix`, `sched.prefix.tier`). Prefix sharing
    and an SLO policy are iteration-level: static batching is the
    reservation baseline the comparison measures.

    `window` = (window, rows the largest forward writes a slot) gives
    the scheduler a second, windowed layer group beside that pool
    (pool.WindowGroup, `sched.window`), sized to full coverage. What
    cannot yet mean anything for such a group is refused here: a
    prefix hit (which layers would it be a hit for? a windowed layer
    forgot the prefix) and with it the host tier's spill of prefix
    pages.

    `states` says the model also keeps a recurrent state a slot (a
    group without pages, paged_cache.SlotStates). The scheduler has
    nothing to account for it -- a slot's state is the slot's, and the
    forward that starts a request at position 0, admitted or readmitted
    after a preemption, starts it from zero -- but counts those starts
    (`sched.state_resets`), and a prefix hit is refused: it would start
    a request past position 0 with pages to share and no state to
    start from."""
    if states and prefix:
        raise ValueError(
            "a prefix hit shares K/V pages and starts the request behind "
            "them; this model's linear layers would need the state the "
            "prefix left, and nobody kept it (ROADMAP R6)")
    if window is not None and host_pages > 0:
        raise ValueError(
            "spill moves the prefix cache's pages of ONE layer group; a "
            "model with a windowed group beside the global one has two")
    if window is not None and prefix:
        raise ValueError(
            "prefix sharing shares the pages of ONE layer group; what a "
            "hit means for a windowed group, which forgot the prefix, is "
            "not defined yet (ROADMAP R5)")
    if host_pages > 0 and not prefix:
        raise ValueError(
            "host_pages > 0 without prefix=True — the host tier spills "
            "prefix-cache pages; there is nothing to spill"
        )
    if mode not in ("continuous", "static"):
        raise ValueError(f"mode {mode!r}: want 'continuous' or 'static'")
    if mode == "static" and (prefix or policy is not None):
        raise ValueError(
            "prefix sharing / SLO policy apply to continuous "
            "batching only — static is the reservation baseline"
        )
    pool = PagePool(num_pages)
    tier = None
    if host_pages > 0:
        tier = HostTier(host_pages, spill_fn=spill_fn,
                        readmit_fn=readmit_fn, fault_poll=tier_fault_poll,
                        route_keys=route_keys)
    pcache = (PrefixCache(pool, page_size, tier, route_keys=route_keys)
              if prefix else None)
    kw = dict(slots=slots, pool=pool, page_size=page_size, max_len=max_len,
              max_queue=max_queue, prefix=pcache, states=states)
    if window is not None:
        kw["window"] = WindowGroup(
            window=window[0], chunk=window[1], page_size=page_size,
            slots=slots, max_len=max_len)
    if mode == "static":
        return StaticScheduler(**kw)
    if policy is not None:
        return SLOScheduler(policy=policy, **kw)
    return ContinuousScheduler(**kw)


class StepOutcome:
    """What one step did: the scheduling moments a tick record names
    (`tick_fields`) and what the drivers decide on."""

    __slots__ = ("swept", "rejected", "admitted", "prefill", "decoded",
                 "spec", "emitted", "progressed", "preempted_pairs",
                 "blocked", "prefix_tick", "new_fin", "new_drop",
                 "state_crc", "window_freed", "state_resets")

    @property
    def moved(self) -> bool:
        """Whether a settled step changed anything at all: device work,
        or a request swept, rejected, admitted or ended — the fleet's
        idle test. `progressed` alone (device work or a drain) is the
        engine's, which sleeps on the next arrival."""
        return self.progressed or bool(
            self.swept or self.rejected or self.admitted
            or self.new_fin or self.new_drop)


class ServeCore:
    """One scheduler's steppable serving loop (module doc).

    `on_emit(req, tok, now)` is called AFTER the token lands in the
    core's own request (a fleet replica's local copy always advances —
    a zombie keeps generating; only the fence decides whether the
    authoritative output accepts it). `on_prefill_done(core, slot,
    now)` is called when a slot's prefill completes with decode work
    remaining: the fleet may detach the slot there for a cross-pool
    handoff (ISSUE 13)."""

    def __init__(self, compute, sched, *, proposer=None, spec_k: int = 8,
                 clock=None, spans=None, on_emit=None,
                 on_prefill_done=None):
        self.compute = compute
        self.sched = sched
        self.prefix = sched.prefix
        self.tier = sched.prefix.tier if sched.prefix is not None else None
        self.proposer = proposer
        self.spec_k = spec_k
        self.spec_stats = empty_spec_fields()
        # Digest framing (ISSUE 14/15): spec-off (0, 0), speculation
        # (1, k). A proposer that carries pool state of its own (the
        # paged draft cache, ISSUE 17) extends the frame per tick with
        # its `digest_state()`; the longer frame can never alias the
        # shorter one (state_digest length-frames the extra block).
        self._digest_extra = (1, spec_k) if proposer is not None else (0, 0)
        self._proposer_state = getattr(proposer, "digest_state", None)
        self._now = 0.0
        self.clock = clock if clock is not None else self._step_now
        self.spans = spans
        self.on_emit = on_emit
        self.on_prefill_done = on_prefill_done
        self.steps = 0
        self.decode_ticks = 0
        self.prefill_chunks = 0
        # Terminal-request watermarks: sched.finished / sched.dropped
        # are append-only, so the new tail since the last step IS that
        # step's terminal set.
        self._n_fin = 0
        self._n_drop = 0

    def _step_now(self) -> float:
        return self._now

    def submit(self, req: Request) -> None:
        self.sched.submit([req])

    @property
    def unfinished(self) -> int:
        return self.sched.unfinished

    def _emit(self, req: Request, tok: int, now: float) -> None:
        req.out.append(tok)
        if req.first_token_at is None:
            req.first_token_at = now
        if self.on_emit is not None:
            self.on_emit(req, tok, now)

    def step(self, now: float, sweep: bool = True) -> StepOutcome:
        """One whole iteration, for a driver with nothing of its own to
        do between the device work and the bookkeeping."""
        return self.settle(self.work(now, sweep))

    def work(self, now: float, sweep: bool = True) -> StepOutcome:
        """The iteration up to the static batch's drain. `now` is the
        moment the schedule is judged by; later moments come from
        `clock`."""
        sched, spans, clock = self.sched, self.spans, self.clock
        self._now = now
        self.steps += 1
        out = StepOutcome()
        out.swept = sched.sweep(now) if sweep else ()
        out.admitted = [[s.idx, s.req.rid] for s in sched.admit(now)]
        # Backpressure AFTER admission: the bound applies to what
        # remains waiting once free slots have been filled.
        out.rejected = (sched.enforce_queue_bound(now)
                        if sched.max_queue is not None else ())
        progressed = False
        prefill_rec = None
        emitted = 0

        # At most ONE prefill chunk per iteration: long prompts advance
        # without starving in-flight decodes.
        if spans is not None:
            spans.enter("prefill.build")
        slot = sched.prefill_slot()
        if slot is not None:
            if slot.cow is not None:
                # Copy-on-write (ISSUE 9): duplicate the partially
                # matched shared page into the slot's private page
                # BEFORE its first write lands there.
                self.compute.copy_page(*slot.cow)
                sched.cow_complete(slot)
            sched.window_step(slot)
            if slot.cached == 0 and sched.state_resets is not None:
                sched.state_resets += 1     # this chunk starts from zero
            n, nxt = self.compute.prefill_chunk(slot)
            slot.cached += n
            self.prefill_chunks += 1
            prefill_rec = [slot.idx, slot.req.rid, n]
            progressed = True
            if slot.cached >= slot.target:
                # Prefill complete: the full prompt's pages are now
                # adoptable into the prefix tree (ISSUE 9), and the
                # chunk's last valid logits give the first generated
                # token right now (TTFT is paid here, not at the next
                # decode tick).
                if spans is not None:
                    # The chunk runs on the device from here to the
                    # read: the adoption below is hidden by it.
                    spans.enter("prefill.wait")
                sched.note_prefill_complete(slot)
                # Sanctioned sync: int() ONLY on the completing chunk,
                # where the token is emitted — mid-prompt chunks
                # pipeline the device array untouched. A recorder
                # times the copy apart from the wait (part `fetch`).
                if spans is not None:
                    first = spans.fetch(nxt, int)
                else:
                    # mctpu: disable=MCT007
                    first = int(nxt)
                now = clock()
                if spans is not None:
                    spans.enter("emit", now)
                self._emit(slot.req, first, now)
                prefill_rec.append("emit")  # first token at completion
                emitted = 1
                if slot.req.done:
                    if sched.release_at_once:
                        sched.finish(slot, clock())
                elif self.on_prefill_done is not None:
                    self.on_prefill_done(self, slot, now)

        now = clock()
        if spans is not None:
            spans.enter("grow", now)
        speculating = self.proposer is not None
        dslots = sched.grow_for_decode(
            now, spec_k=self.spec_k if speculating else 1)
        out.decoded = [[s.idx, s.req.rid] for s in dslots]
        for s in dslots:
            sched.window_step(s)
        spec_rec = None
        if dslots and spans is not None:
            spans.enter("tick.build")
        if dslots and speculating:
            # Speculative round (ISSUE 14): propose per slot, ONE
            # batched verify block, greedy acceptance — each slot
            # commits 1..k tokens; commit_spec rolls rejected-draft
            # pages back into the pool.
            widths = [sched.spec_width(s, self.spec_k) for s in dslots]
            results = run_round(dslots, widths, self.proposer,
                                self.compute.verify)
            self.decode_ticks += 1
            now = clock()
            if spans is not None:
                spans.enter("emit", now)
            spec_rec = []
            stats = self.spec_stats
            for s, w, j, toks_out in results:
                sched.commit_spec(s, j)
                for t in toks_out:
                    self._emit(s.req, t, now)
                emitted += j
                spec_rec.append([s.req.rid, w - 1, j - 1])
                stats["spec_rounds"] += 1
                stats["spec_proposed"] += w - 1
                stats["spec_accepted"] += j - 1
                if s.req.done and sched.release_at_once:
                    sched.finish(s, now)
            progressed = True
        elif dslots:
            toks = self.compute.decode(dslots)
            self.decode_ticks += 1
            now = clock()
            if spans is not None:
                spans.enter("emit", now)
            for s in dslots:
                s.cached += 1
                self._emit(s.req, int(toks[s.idx]), now)
                if s.req.done and sched.release_at_once:
                    sched.finish(s, now)
            emitted += len(dslots)
            progressed = True

        if sched.batch_done():
            sched.drain(clock())
            progressed = True
        out.prefill = prefill_rec
        out.spec = spec_rec
        out.emitted = emitted
        out.progressed = progressed
        return out

    def settle(self, out: StepOutcome) -> StepOutcome:
        """The iteration's bookkeeping, paid in every run: the drains,
        the new terminal tails and the end-of-iteration state digest
        (ISSUE 15: the ONE scheduler_digest spelling, O(slots))."""
        sched = self.sched
        # (victim, beneficiary) pairs: the causal edges of ISSUE 11.
        out.preempted_pairs = sched.drain_preempted()
        out.blocked = sched.drain_blocked()
        out.prefix_tick = (self.prefix.drain_tick()
                           if self.prefix is not None else None)
        out.window_freed = (sched.window.drain_freed()
                            if sched.window is not None else None)
        out.state_resets = sched.state_resets
        if sched.state_resets:
            sched.state_resets = 0
        out.new_fin = sched.finished[self._n_fin:]
        out.new_drop = sched.dropped[self._n_drop:]
        self._n_fin, self._n_drop = len(sched.finished), len(sched.dropped)
        extra = self._digest_extra
        if self._proposer_state is not None:
            extra = (*extra, *self._proposer_state())
        out.state_crc = scheduler_digest(sched, extra=extra)
        return out

    def tick_fields(self, out: StepOutcome) -> dict:
        """The tick record's fields every driver shares (obs `tick`
        event shape), from a settled step: its scheduling moments and
        the end-of-iteration gauges. A driver lays them out in its own
        record's key order and adds what only it has."""
        sched = self.sched
        fields = {
            "running": sum(1 for s in sched.slots if not s.free),
            "free_pages": sched.pool.free_pages,
            "admitted": out.admitted, "prefill": out.prefill,
            "decoded": out.decoded,
            "finished": [r.rid for r in out.new_fin],
            "aborted": [[r.rid, r.status] for r in out.new_drop],
            # The rid list keeps the pre-ISSUE-11 tick shape; the pairs
            # below are the causal edges.
            "preempted": [v for v, _ in out.preempted_pairs],
            # Causality (ISSUE 11): blocked admission attempts ([rid,
            # reason, holders]) and preemption beneficiaries ([victim,
            # for_rid]) — the blocker edges of the blame DAG `mctpu
            # explain` reconstructs.
            "blocked": [[rid, reason, holders]
                        for rid, reason, holders in out.blocked],
            "preempted_for": [[v, b] for v, b in out.preempted_pairs
                              if b is not None],
            # Flight recorder (ISSUE 15): crc32 of the canonical
            # host-side state after this iteration — `mctpu replay`
            # recomputes it from the events above at every tick.
            "state_crc": out.state_crc,
        }
        if sched.window is not None:
            # A windowed layer group (pool.WindowGroup): the pages
            # issued at the iteration's end, [global group, windowed
            # group], and the pages the windowed group gave back
            # behind windows in this iteration.
            fields["pages_held"] = [p.usable - p.free_pages
                                    for p in (sched.pool, sched.window.pool)]
            fields["window_pages_freed"] = out.window_freed
        if out.state_resets is not None:
            # A model with a recurrent state a slot: the pages issued,
            # as above, and the slots whose state this iteration's
            # chunk started from zero (an admission or a readmission).
            fields["pages_held"] = [sched.pool.usable - sched.pool.free_pages]
            fields["state_resets"] = out.state_resets
        if out.spec is not None:
            # Speculative round detail (ISSUE 14): [rid, proposed,
            # accepted] per slot — `mctpu trace` derives the round's
            # emitted count (1 + accepted) from it, so the token
            # cross-check survives variable-length commits.
            fields["spec"] = out.spec
        if out.prefix_tick is not None:
            # Prefix-cache fields (ISSUE 9): this tick's hit markers
            # ([rid, matched_tokens] — the lifecycle event `mctpu
            # trace` renders) + the cumulative tree stats the replay
            # reconstruction adopts its cow/insert/eviction deltas from.
            fields["prefix_hits"] = out.prefix_tick["hits"]
            fields["prefix"] = {"shared_pages": self.prefix.shared_pages,
                                **self.prefix.stats}
            if self.tier is not None:
                # Host-tier fields (ISSUE 17): cumulative spill /
                # readmit / refusal / host-eviction counters + occupancy
                # on the same dict, and this tick's readmit lifecycle
                # markers ([rid, tokens] — the `mctpu trace` event).
                fields["prefix"].update(self.tier.stats)
                fields["prefix"]["host_used"] = self.tier.host_used
                fields["prefix_readmits"] = out.prefix_tick["readmits"]
        return fields

    def prefix_stats(self) -> dict:
        """Cumulative prefix counters in the flat summary shape (zeros
        with sharing off — gated metrics exist in every run)."""
        if self.prefix is None:
            return empty_prefix_fields()
        return self.prefix.summary_fields()

    def reset_prefix_stats(self) -> None:
        """Zero the counters after they were banked (retirement at
        failover: a zombie's later activity must not re-bank)."""
        if self.prefix is not None:
            for k in self.prefix.stats:
                self.prefix.stats[k] = 0
        if self.tier is not None:
            for k in self.tier.stats:
                self.tier.stats[k] = 0

    def reset_spec_stats(self) -> None:
        """Spec-counter twin of reset_prefix_stats."""
        self.spec_stats = empty_spec_fields()


def observe_tick(registry, rec: dict) -> None:
    """Fold one tick record's shared fields into a MetricsRegistry: the
    load gauges (what least-loaded dispatch reads) and the per-tick
    counters. A driver adds the metrics only it has."""
    registry.set("serve.queue_depth", rec["queue"])
    registry.set("serve.running_slots", rec["running"])
    registry.set("serve.free_pages", rec["free_pages"])
    if rec["decoded"]:
        registry.inc("serve.decode_ticks")
    if rec["prefill"] is not None:
        registry.inc("serve.prefill_chunks")
    if rec["preempted"]:
        registry.inc("serve.preemptions", len(rec["preempted"]))
    if rec.get("prefix_hits"):
        registry.inc("serve.prefix.hits", len(rec["prefix_hits"]))
        registry.inc("serve.prefix.hit_tokens",
                     sum(m for _, m in rec["prefix_hits"]))
    if rec.get("spec"):
        registry.inc("serve.spec.rounds", len(rec["spec"]))
        registry.inc("serve.spec.proposed",
                     sum(p for _, p, _ in rec["spec"]))
        registry.inc("serve.spec.accepted_total",
                     sum(a for _, _, a in rec["spec"]))
