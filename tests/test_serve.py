"""Serving engine (mpi_cuda_cnn_tpu/serve/): paged-cache parity with the
contiguous decode path, page-pool accounting invariants, and the
continuous-vs-static scheduler comparison — all deterministic on CPU."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_cnn_tpu.models.generate import decode_step, generate, init_cache
from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
from mpi_cuda_cnn_tpu.serve.paged_cache import (
    PagePool,
    init_paged_cache,
    pages_for,
)
from mpi_cuda_cnn_tpu.serve.core import ServeCore, build_scheduler
from mpi_cuda_cnn_tpu.serve.pool import WindowGroup
from mpi_cuda_cnn_tpu.serve.scheduler import (
    ContinuousScheduler,
    Request,
    _SchedulerBase,
)

MODEL = TransformerLM(vocab=13, dim=32, heads=4, depth=2, max_seq=48)
GQA = TransformerLM(vocab=13, dim=32, heads=4, depth=2, max_seq=48,
                    kv_heads=2, pos="rope")


def _identity_paged_cache(model, batch, page_size, dtype=jnp.float32):
    """A paged cache whose block tables cover max_seq per row with
    ascending page indices — the layout the layer-level parity loops
    drive through decode_step's PagedKVCache dispatch."""
    per = pages_for(model.max_seq, page_size)
    cache = init_paged_cache(model, slots=batch,
                             num_pages=batch * per + 1,
                             page_size=page_size, dtype=dtype)
    table = 1 + np.arange(batch * per, dtype=np.int32).reshape(batch, per)
    return dataclasses.replace(cache, block_table=jnp.asarray(table))


@pytest.mark.parametrize("model", [MODEL, GQA], ids=["mha", "gqa_rope"])
def test_paged_decode_step_matches_contiguous_f32(model):
    """decode_step over a PagedKVCache (per-slot positions) must equal
    the contiguous cache BITWISE in f32: the two layouts share the
    attention read (generate.attend_kv) and differ only in how cache
    rows are materialized, so any drift is a layout bug, not rounding.
    Page size 8 does not divide 20 steps evenly — writes cross page
    boundaries mid-sequence."""
    params = model.init(jax.random.key(0))
    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, 13, (3, 20)), jnp.int32
    )
    cc = init_cache(model, 3)
    pc = _identity_paged_cache(model, 3, page_size=8)
    for i in range(20):
        want, cc = decode_step(model, params, toks[:, i], i, cc)
        got, pc = decode_step(model, params, toks[:, i],
                              jnp.full((3,), i, jnp.int32), pc)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"step {i}")


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_paged_decode_step_matches_contiguous_quantized(dtype):
    """bf16/int8 paged caches quantize EXACTLY like the contiguous ones
    (same per-(position, head) absmax contract), so the two layouts stay
    within tight float tolerance of each other — far inside the
    cache-dtype error bands the contiguous tests pin vs f32."""
    params = MODEL.init(jax.random.key(0))
    toks = jnp.asarray(
        np.random.default_rng(3).integers(0, 13, (2, 16)), jnp.int32
    )
    cc = init_cache(MODEL, 2, jnp.dtype(dtype))
    pc = _identity_paged_cache(MODEL, 2, page_size=8, dtype=jnp.dtype(dtype))
    assert pc.pages[0]["k"].dtype == jnp.dtype(dtype)
    for i in range(16):
        want, cc = decode_step(MODEL, params, toks[:, i], i, cc)
        got, pc = decode_step(MODEL, params, toks[:, i],
                              jnp.full((2,), i, jnp.int32), pc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5, err_msg=f"step {i}")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_engine_greedy_generations_match_generate(dtype):
    """End-to-end: the engine's chunked-prefill + paged-decode greedy
    continuations equal models/generate.generate's contiguous ones for
    every request — across cache dtypes, prompt lengths that don't
    divide the prefill chunk, and both scheduler modes."""
    params = MODEL.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 13, (n,)).astype(np.int32)
               for n in (3, 7, 11, 5)]
    new = [9, 4, 12, 7]
    want = [
        np.asarray(generate(MODEL, params, jnp.asarray(p[None, :]), n,
                            cache_dtype=dtype))[0]
        for p, n in zip(prompts, new)
    ]
    engine = PagedEngine(MODEL, params, slots=2, num_pages=4 * 6 + 1,
                         page_size=8, prefill_chunk=4, cache_dtype=dtype)
    for mode in ("continuous", "static"):
        reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, new))]
        res = engine.run(reqs, mode=mode)
        assert sorted(r.rid for r in res.requests) == [0, 1, 2, 3]
        for r in res.requests:
            np.testing.assert_array_equal(
                np.asarray(r.out), want[r.rid],
                err_msg=f"{mode} request {r.rid} ({dtype})"
            )


def test_static_holds_slot_when_request_finishes_at_prefill():
    """A max_new_tokens=1 request finishes AT prefill completion (its
    only token comes from the last chunk's logits). Under static
    batching that slot must stay reserved until the batch drains —
    finishing it early would release pages mid-batch, breaking the
    reserve-until-drain discipline the comparison measures — and both
    requests must still complete in both modes."""
    params = MODEL.init(jax.random.key(0))
    engine = PagedEngine(MODEL, params, slots=2, num_pages=15, page_size=8)
    for mode in ("static", "continuous"):
        reqs = [Request(rid=0, prompt=np.arange(5) % 13, max_new_tokens=1),
                Request(rid=1, prompt=np.arange(7) % 13, max_new_tokens=10)]
        res = engine.run(reqs, mode=mode)
        assert sorted(r.rid for r in res.requests) == [0, 1]
        assert [len(r.out) for r in
                sorted(res.requests, key=lambda r: r.rid)] == [1, 10]


def test_page_pool_accounting():
    pool = PagePool(8)  # 7 usable, page 0 scratch
    a = pool.try_alloc(3, "a")
    b = pool.try_alloc(2, "b")
    assert a == [1, 2, 3] and b == [4, 5]  # deterministic ascending issue
    assert pool.free_pages == 2
    assert pool.try_alloc(3, "c") is None  # over-ask: no change
    assert pool.free_pages == 2
    pool.check()
    with pytest.raises(RuntimeError, match="owned by"):
        pool.free([4], "a")                # foreign free refused
    pool.free(a, "a")
    with pytest.raises(RuntimeError, match="double free"):
        pool.free(a, "a")
    pool.free(b, "b")
    pool.check()
    assert pool.free_pages == pool.usable


def test_scheduler_admit_finish_preempt_keep_pool_consistent():
    """Drive the continuous scheduler through admit -> decode growth ->
    forced preemption -> finish and assert the pool invariant after
    every transition: no leak, no double-book, scratch page never
    circulates."""
    pool = PagePool(7)  # 6 usable pages of 4 tokens
    sched = ContinuousScheduler(slots=2, pool=pool, page_size=4, max_len=24)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, 13, (8,)), arrival=0.0,
                    max_new_tokens=12) for i in range(3)]
    sched.submit(reqs)
    bound = sched.admit(0.0)
    # 8-token prompts need 2 pages each +1 headroom: both slots admit.
    assert [s.req.rid for s in bound] == [0, 1]
    pool.check()
    assert pool.free_pages == 2
    for s in bound:                       # prefill completes, decode grows
        s.cached = s.target
        s.req.out.append(1)
    assert len(sched.grow_for_decode()) == 2
    pool.check()
    # Burn the remaining pages: advance both slots until the pool runs
    # dry and the LATEST-admitted sequence gets preempted.
    while sched.preemptions == 0:
        for s in list(sched.decode_slots()):
            s.cached += 1
            s.req.out.append(1)
        sched.grow_for_decode()
        pool.check()
    assert sched.slots[1].free            # victim = latest admitted
    assert reqs[1].preemptions == 1
    assert sched.queue[0].rid == 1        # requeued at the head
    sched.finish(sched.slots[0], now=1.0)
    pool.check()
    assert reqs[0].finished_at == 1.0
    # Everything freed once the survivor finished.
    assert pool.free_pages == pool.usable - 0 - len(sched.slots[1].pages)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_pagepool_randomized_op_sequence_invariant(dtype):
    """Seeded randomized-sequence invariant (ISSUE 7 satellite,
    extended for ISSUE 9 and again for ISSUE 13): a few hundred random
    admit / prefill-chunk / decode-growth / preempt / cancel / expire
    operations — interleaved with prefix-cache share / acquire / COW /
    insert / LRU-evict / release traffic (half the prompts draw from a
    shared template pool, a reclaim op squeezes retained pages out)
    AND with cross-pool KV-handoff traffic against a SECOND
    engine+pool+scheduler (detach-for-handoff seals pages under the
    transfer token, the receiver adopts via the cross-engine page copy
    and binds decode-ready, and a random half of the transfers are
    REVOKED mid-flight instead — both ends released) — against real
    PagedEngine caches in each storage dtype, with the extended
    sched.check() (pool no-leak / no-double-book / scratch-never-
    circulates PLUS refcount conservation and no-writable-shared-page)
    on BOTH pools after EVERY step. ISSUE 14 adds speculative rounds:
    a spec decode op grows toward the k-row verify width, commits a
    VARIABLE number of tokens (whatever greedy acceptance yields), and
    commit_spec's rejected-draft ROLLBACK hands surplus pages back —
    the walk must observe both a multi-token commit and a rollback.
    ISSUE 17 bolts a bounded HostTier onto scheduler A's prefix cache:
    LRU reclaims SPILL real engine KV pages to host entries, later
    template walks READMIT them through fresh allocations, and a
    corrupt-seal op arms the kv_corrupt injector so at least one
    lookup REFUSES a flipped stamp and degrades to re-prefill — all
    under the same every-step check().
    ISSUE 19 adds the autoscaler's membership moves as walk ops: a
    JOIN op brings up a whole new engine+pool+scheduler member
    mid-walk, a dispatch op routes queued work onto joined members,
    and a GRACEFUL-DRAIN op stops a member's admissions and requeues
    its waiting work back while in-flight slots run to completion —
    every member's pool under the same every-step check(), and every
    drained member's pool must hand back every page.
    ISSUE 20 puts a REAL TransportBus under part of the traffic:
    a bus-dispatch op sends requests to scheduler A over the wire
    (some copies DELAYED in flight — a request on the wire is in no
    scheduler, so the every-step check() proves wire state never
    leaks into a pool), a harvest op reports terminal requests back
    over the bus with DUPLICATED copies the receiver must dedup, and
    two sampled PARTITION windows open and heal mid-walk — reliable
    sends retransmit through them and every bus-dispatched request
    still arrives exactly once.
    The fleet's re-dispatch and disaggregated-handoff paths
    (serve/fleet.py) drive these exact scheduler+pool+prefix triples
    per replica, so they inherit the guarantee."""
    from mpi_cuda_cnn_tpu.serve.host_tier import HostTier
    from mpi_cuda_cnn_tpu.serve.prefix_cache import PrefixCache
    from mpi_cuda_cnn_tpu.serve.spec import LookupProposer, run_round

    params = MODEL.init(jax.random.key(2))
    engine = PagedEngine(MODEL, params, slots=3, num_pages=10, page_size=4,
                         prefill_chunk=4, max_len=32, cache_dtype=dtype,
                         spec="lookup", spec_k=4)
    # Host pool sized to the engine's device page arrays — the pairing
    # ServeCore uses: page indices from this pool index those arrays.
    pool = PagePool(10)
    # Host tier on A (ISSUE 17): real engine spill/readmit callbacks —
    # evicted KV rows round-trip through host memory — plus an armable
    # corrupt-seal injector (the kv_corrupt@tier.spill path).
    corrupt_pending = [0]

    class _Corrupt:
        kind = "kv_corrupt"

    def tier_poll(seq):
        if corrupt_pending[0]:
            corrupt_pending[0] -= 1
            return [_Corrupt]
        return []

    tier = HostTier(4, spill_fn=engine.spill_page,
                    readmit_fn=engine.readmit_page, fault_poll=tier_poll)
    prefix = PrefixCache(pool, page_size=4, tier=tier)
    sched = ContinuousScheduler(slots=3, pool=pool, page_size=4, max_len=32,
                                prefix=prefix)
    # The decode-side twin (ISSUE 13): its own engine/pool/scheduler —
    # handed-off requests decode (and, after a preemption there,
    # re-prefill) on this pair.
    engine_b = PagedEngine(MODEL, params, slots=3, num_pages=10,
                           page_size=4, prefill_chunk=4, max_len=32,
                           cache_dtype=dtype, spec="lookup", spec_k=4)
    pool_b = PagePool(10)
    sched_b = ContinuousScheduler(slots=3, pool=pool_b, page_size=4,
                                  max_len=32,
                                  prefix=PrefixCache(pool_b, page_size=4))
    transfers = {"done": 0, "revoked": 0}
    next_hid = [0]
    rng = np.random.default_rng(11)
    # Shared template prompts: same-template requests exercise full-page
    # acquire; divergent suffixes at non-page-aligned depths hit COW.
    templates = [rng.integers(0, 13, (9,)).astype(np.int32)
                 for _ in range(2)]
    now = 0.0
    next_rid = 0
    submitted: list[Request] = []

    def submit_one():
        nonlocal next_rid
        if rng.random() < 0.5:
            tmpl = templates[int(rng.integers(len(templates)))]
            keep = int(rng.integers(4, 10))
            tail = rng.integers(0, 13, (int(rng.integers(1, 4)),))
            prompt = np.concatenate([tmpl[:keep], tail.astype(np.int32)])
        else:
            prompt = rng.integers(0, 13, (int(rng.integers(2, 12)),))
        req = Request(
            rid=next_rid, prompt=prompt,
            max_new_tokens=int(rng.integers(2, 14)), arrival=now,
            # ~1 in 4 requests carries a deadline the clock will cross.
            deadline=(now + float(rng.uniform(0.05, 0.6))
                      if rng.random() < 0.25 else None),
        )
        next_rid += 1
        submitted.append(req)
        sched.submit([req])

    def prefill_step(sc=None, en=None):
        sc, en = sc or sched, en or engine
        slot = sc.prefill_slot()
        if slot is None:
            return
        if slot.cow is not None:
            en.copy_page(*slot.cow)
            sc.cow_complete(slot)
        n, nxt = en.run_prefill_chunk(slot)
        slot.cached += n
        if slot.cached >= slot.target:
            sc.note_prefill_complete(slot)
            slot.req.out.append(int(nxt))
            if slot.req.done:
                sc.finish(slot, now)

    def decode_step_op(sc=None, en=None):
        sc, en = sc or sched, en or engine
        dslots = sc.grow_for_decode(now)
        if not dslots:
            return
        toks = en.run_decode_tick(dslots)
        for s in dslots:
            s.cached += 1
            s.req.out.append(int(toks[s.idx]))
            if s.req.done:
                sc.finish(s, now)

    proposer = LookupProposer(ngram=2)
    spec_seen = {"rounds": 0, "multi": 0, "rollbacks": 0}

    def spec_decode_op(sc=None, en=None):
        # Speculative round (ISSUE 14): grow toward the k-row verify
        # width, ONE batched verify, variable-length commit, rollback
        # of rejected-draft pages.
        sc, en = sc or sched, en or engine
        dslots = sc.grow_for_decode(now, spec_k=4)
        if not dslots:
            return
        widths = [sc.spec_width(s, 4) for s in dslots]
        results = run_round(dslots, widths, proposer, en.run_spec_tick)
        for s, w, j, toks in results:
            pages_before = len(s.pages)
            sc.commit_spec(s, j)
            spec_seen["rounds"] += 1
            spec_seen["multi"] += j > 1
            spec_seen["rollbacks"] += len(s.pages) < pages_before
            s.req.out.extend(toks)
            if s.req.done:
                sc.finish(s, now)

    def preempt_op():
        bound = [s for s in sched.slots if not s.free]
        if bound:
            sched.preempt(bound[int(rng.integers(len(bound)))])

    def cancel_op():
        live = [r for r in submitted if not r.terminal]
        if live:
            live[int(rng.integers(len(live)))].cancel()
            sched.sweep(now)
            sched_b.sweep(now)

    def reclaim_op():
        # The squeeze/pressure path: evict up to 2 LRU refcount-0
        # prefix pages (never a referenced one — free() would raise).
        # With the tier attached each eviction SPILLS instead of
        # discarding — the pressure op doubles as the spill op.
        prefix.reclaim(int(rng.integers(1, 3)))

    def corrupt_op():
        # Arm the injector: the NEXT spill seals a flipped stamp, so a
        # later matching tier lookup must refuse it (counted) and fall
        # back to a plain miss — the re-prefill degrade path.
        corrupt_pending[0] += 1

    def handoff_op():
        # Cross-pool transfer (ISSUE 13): seal a decoding slot's page
        # set off scheduler A under the handoff token, then either
        # adopt it into B (cross-engine page copy + decode-ready bind)
        # or REVOKE the transfer mid-flight — both ends released, the
        # request requeued at A's head (the abort-re-prefill path).
        cands = [s for s in sched.slots
                 if s.decoding and not s.req.terminal and s.cow is None]
        if not cands:
            return
        slot = cands[int(rng.integers(len(cands)))]
        req, cached = slot.req, slot.cached
        owner = ("handoff", req.rid, next_hid[0])
        next_hid[0] += 1
        pages, private, nodes = sched.detach_for_handoff(slot, owner)
        dst = pool_b.try_alloc(len(pages), owner)
        if dst is None or rng.random() < 0.5:
            # Revoked (receiver dry, dropped, or CRC-refused): release
            # both ends, requeue for re-prefill on A.
            if dst is not None:
                pool_b.free(dst, owner)
            sched.release_handoff(private, nodes, owner)
            req.status = "queued"
            sched.queue.appendleft(req)
            transfers["revoked"] += 1
            return
        engine_b.adopt_pages(engine, pages, dst)
        bound = sched_b.bind_transfer(req, dst, cached, owner, now)
        if bound is None:
            # No free receiver slot: treat as a revoke (the fleet
            # would keep waiting; the invariant walk releases).
            pool_b.free(dst, owner)
            sched.release_handoff(private, nodes, owner)
            req.status = "queued"
            sched.queue.appendleft(req)
            transfers["revoked"] += 1
            return
        sched.release_handoff(private, nodes, owner)
        transfers["done"] += 1

    # Autoscaler membership moves (ISSUE 19): joined members are whole
    # engine+pool+scheduler triples appearing MID-WALK, exactly what a
    # replica_join brings up; graceful drain is the scale-down leg.
    members: list[dict] = []
    scale = {"joins": 0, "dispatches": 0, "drains": 0}

    def join_op():
        if len(members) >= 2:
            return
        p = PagePool(10)
        e = PagedEngine(MODEL, params, slots=3, num_pages=10, page_size=4,
                        prefill_chunk=4, max_len=32, cache_dtype=dtype,
                        spec="lookup", spec_k=4)
        s = ContinuousScheduler(slots=3, pool=p, page_size=4, max_len=32,
                                prefix=PrefixCache(p, page_size=4))
        members.append({"sched": s, "engine": e, "pool": p,
                        "draining": False})
        scale["joins"] += 1

    def member_dispatch_op():
        # Route queued work onto a joined member — the autoscaler's
        # whole point: new capacity takes load off the loaded one.
        live = [m for m in members if not m["draining"]]
        if not live or not sched.queue:
            return
        m = live[int(rng.integers(len(live)))]
        m["sched"].submit([sched.queue.popleft()])
        scale["dispatches"] += 1

    def member_step_op():
        if not members:
            return
        m = members[int(rng.integers(len(members)))]
        m["sched"].sweep(now)
        if not m["draining"]:
            m["sched"].admit(now)
        prefill_step(m["sched"], m["engine"])
        decode_step_op(m["sched"], m["engine"])

    def drain_op():
        # Graceful drain: no new admissions, waiting work requeues back
        # to A, in-flight slots run to completion — the member's pool
        # must end the walk with every page handed back.
        live = [m for m in members if not m["draining"]]
        if not live:
            return
        m = live[int(rng.integers(len(live)))]
        m["draining"] = True
        while m["sched"].queue:
            sched.queue.append(m["sched"].queue.popleft())
        scale["drains"] += 1

    def check_both():
        sched.check()
        sched_b.check()
        for m in members:
            m["sched"].check()

    # Lossy-transport ops (ISSUE 20): a real TransportBus carries part
    # of the dispatch traffic into scheduler A and harvest reports
    # back out, with delayed dispatches, duplicated harvest reports
    # and two partition windows armed on the bus's own fault injector.
    from mpi_cuda_cnn_tpu.faults import FaultInjector
    from mpi_cuda_cnn_tpu.serve.transport import TransportBus

    bus_tick = [0]
    wire = {"dispatched": 0, "harvests": 0}
    wire_rids: set = set()
    harvest_seen: set = set()

    def _router_msg(msg, tick):
        # Receiver-side dedup makes the duplicated harvest report a
        # single logical delivery.
        assert msg.key not in harvest_seen, "bus dedup failed"
        harvest_seen.add(msg.key)

    def _member_msg(msg, tick):
        req = msg.payload
        assert req.rid not in wire_rids, "duplicate dispatch delivery"
        wire_rids.add(req.rid)
        sched.submit([req])

    bus = TransportBus(faults=FaultInjector(
        "msg_delay@fleet.transport:8?kind=dispatch&count=3&ticks=4;"
        "msg_dup@fleet.transport:30?kind=commit&count=3;"
        "partition@fleet.transport:60?replica=0&ticks=10;"
        "partition@fleet.transport:150?replica=0&ticks=8"))
    bus.register("router", _router_msg)
    bus.register("r0#0", _member_msg)

    def bus_dispatch_op():
        nonlocal next_rid
        prompt = rng.integers(0, 13, (int(rng.integers(2, 12)),))
        req = Request(rid=next_rid, prompt=prompt,
                      max_new_tokens=int(rng.integers(2, 14)),
                      arrival=now)
        next_rid += 1
        submitted.append(req)
        wire["dispatched"] += 1
        bus.send("dispatch", "router", "r0#0", req, tick=bus_tick[0],
                 key=(req.rid, "d", 0), reliable=True)

    def bus_harvest_op():
        done = [r for r in submitted if r.terminal]
        if not done:
            return
        r = done[int(rng.integers(len(done)))]
        wire["harvests"] += 1
        bus.send("commit", "r0#0", "router",
                 {"rid": r.rid, "outlen": len(r.out)},
                 tick=bus_tick[0], key=(r.rid, "c", 0, len(r.out)),
                 reliable=True)

    def bus_step():
        bus_tick[0] += 1
        bus.apply_tick_faults(bus_tick[0])
        bus.pump(bus_tick[0])

    ops = [submit_one, lambda: sched.admit(now), prefill_step,
           decode_step_op, preempt_op, cancel_op,
           lambda: sched.sweep(now), reclaim_op, handoff_op,
           lambda: decode_step_op(sched_b, engine_b),
           lambda: sched_b.admit(now),
           lambda: prefill_step(sched_b, engine_b),
           spec_decode_op,
           lambda: spec_decode_op(sched_b, engine_b),
           corrupt_op,
           join_op, member_dispatch_op, member_step_op, drain_op,
           bus_dispatch_op, bus_harvest_op]
    weights = np.array([0.16, 0.14, 0.15, 0.06, 0.06, 0.04, 0.04, 0.04,
                        0.09, 0.04, 0.03, 0.03, 0.06, 0.04, 0.02,
                        0.02, 0.04, 0.05, 0.02,
                        0.05, 0.04])
    weights = weights / weights.sum()
    for _ in range(340):
        now += float(rng.uniform(0.0, 0.02))  # deadlines really expire
        bus_step()
        ops[int(rng.choice(len(ops), p=weights))]()
        check_both()
    # Drain every scheduler AND the wire: the surviving work must
    # complete and hand every page of every pool back — including the
    # autoscaler-joined members', draining or not — and every delayed
    # or unacked bus message must deliver or drop (a bus-dispatched
    # request still on the wire is in no scheduler yet).
    while (sched.unfinished or sched_b.unfinished
           or any(m["sched"].unfinished for m in members)
           or bus.busy()):
        bus_step()
        for sc, en in ((sched, engine), (sched_b, engine_b),
                       *((m["sched"], m["engine"]) for m in members)):
            sc.sweep(now)
            sc.admit(now)
            prefill_step(sc, en)
            decode_step_op(sc, en)
        check_both()
        now += 0.01
    assert all(r.terminal for r in submitted)
    prefix.clear()   # retained LRU pages hand back at teardown
    sched_b.prefix.clear()
    for m in members:
        m["sched"].prefix.clear()
    check_both()
    assert pool.free_pages == pool.usable
    assert pool_b.free_pages == pool_b.usable
    for m in members:
        assert m["pool"].free_pages == m["pool"].usable
    # The randomized walk must have exercised the interesting paths —
    # including the whole ISSUE 9 surface.
    assert sched.preemptions > 0
    statuses = {r.status for r in submitted}
    assert "finished" in statuses
    assert statuses & {"expired", "cancelled"}
    assert prefix.stats["hits"] > 0
    assert prefix.stats["cow_copies"] > 0
    assert prefix.stats["inserts"] > 0
    assert prefix.stats["evictions"] > 0
    # The cross-pool surface (ISSUE 13): both the adopt and the revoke
    # legs of the transfer protocol ran.
    assert transfers["done"] > 0
    assert transfers["revoked"] > 0
    # The speculative surface (ISSUE 14): rounds ran, at least one
    # committed more than one token, and at least one rollback handed
    # rejected-draft pages back through the ownership check.
    assert spec_seen["rounds"] > 0
    assert spec_seen["multi"] > 0
    assert spec_seen["rollbacks"] > 0
    # The autoscaler-membership surface (ISSUE 19): a member joined
    # mid-walk, took dispatched work, and gracefully drained.
    assert scale["joins"] > 0
    assert scale["dispatches"] > 0
    assert scale["drains"] > 0
    # The host-tier surface (ISSUE 17): pages spilled under pressure,
    # readmitted through fresh allocations on later template walks, and
    # at least one corrupt seal refused by the CRC discipline.
    assert tier.stats["spills"] > 0
    assert tier.stats["readmits"] > 0
    assert tier.stats["refusals"] > 0
    # The lossy-transport surface (ISSUE 20): dispatches crossed the
    # wire and every one arrived exactly once (delayed copies and
    # partition retransmissions included); the duplicated harvest
    # report was collapsed by receiver dedup; both partition windows
    # opened and healed; conservation holds at quiesce.
    f = bus.record_fields()
    assert (f["sent"] == f["delivered"] + f["deduped"] + f["dropped"]
            + f["inflight"])
    assert wire["dispatched"] > 0
    assert len(wire_rids) == wire["dispatched"]
    assert wire["harvests"] > 0
    assert bus.counters["delayed"] > 0
    assert bus.counters["duped"] > 0
    assert bus.counters["deduped"] > 0
    assert bus.counters["retransmits"] > 0
    assert bus.counters["partitions"] == 2
    assert not bus.partitions and not bus.busy()


def test_engine_preemption_recovers_and_completes():
    """A pool far smaller than the workload's worst case forces
    preemptions; recompute must still finish every request with its
    full greedy budget, and the engine's end-of-run invariants (no lost
    requests, zero leaked pages) must hold."""
    params = MODEL.init(jax.random.key(1))
    rng = np.random.default_rng(5)
    engine = PagedEngine(MODEL, params, slots=3, num_pages=10, page_size=4,
                         prefill_chunk=8, max_len=40)
    reqs = [Request(rid=i, prompt=rng.integers(0, 13, (6,)),
                    max_new_tokens=18) for i in range(5)]
    res = engine.run(reqs, mode="continuous")
    assert res.preemptions > 0
    assert sorted(r.rid for r in res.requests) == list(range(5))
    assert all(len(r.out) == 18 for r in res.requests)


def test_continuous_batching_beats_static_on_mixed_lengths():
    """THE tentpole property, deterministically on CPU: with mixed
    output lengths, iteration-level continuous batching finishes the
    workload in FEWER decode ticks than static batching (vacated slots
    readmit mid-flight instead of idling until the batch drains) — and
    greedy token streams are identical per request across modes."""
    params = MODEL.init(jax.random.key(0))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 13, (4,)).astype(np.int32) for _ in range(8)]
    lens = [3, 24, 3, 24, 3, 24, 3, 24]   # short/long mix: static pays
    #                                       the long tail in every batch
    engine = PagedEngine(MODEL, params, slots=2, num_pages=33, page_size=4,
                         prefill_chunk=8, max_len=32)

    def workload():
        return [Request(rid=i, prompt=p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, lens))]

    static = engine.run(workload(), mode="static")
    cont = engine.run(workload(), mode="continuous")
    assert static.output_tokens == cont.output_tokens == sum(lens)
    assert cont.decode_ticks < static.decode_ticks
    by_rid = {r.rid: r.out for r in static.requests}
    for r in cont.requests:
        assert r.out == by_rid[r.rid], f"request {r.rid} diverged"


def test_request_records_schema_validate_and_report():
    """Per-request engine records round-trip the obs schema (strict
    validation) and surface in `mctpu report`'s serving tables."""
    from mpi_cuda_cnn_tpu.obs.report import summarize
    from mpi_cuda_cnn_tpu.obs.schema import make_record, validate_record

    params = MODEL.init(jax.random.key(0))
    engine = PagedEngine(MODEL, params, slots=2, num_pages=13, page_size=8)
    reqs = [Request(rid=i, prompt=np.arange(4) % 13, max_new_tokens=5)
            for i in range(3)]
    res = engine.run(reqs, mode="continuous")
    records = [validate_record(make_record("request", 0.1, **rec))
               for rec in res.request_records()]
    records.append(validate_record(
        make_record("serve", 0.2, **res.summary())
    ))
    s = summarize(records)
    assert s["requests"][0]["mode"] == "continuous"
    assert s["requests"][0]["requests"] == 3
    assert s["requests"][0]["output_tokens"] == 15
    assert s["serve"][0]["decode_ticks"] == res.decode_ticks
    assert s["serve"][0]["tokens_per_s"] > 0


def test_serve_bench_cli_runs_and_emits_valid_jsonl(tmp_path):
    """The `mctpu serve-bench` surface end-to-end: both modes run, the
    comparison line prints, and the JSONL sink strict-validates."""
    import json

    from mpi_cuda_cnn_tpu.serve.bench import serve_bench_main
    from mpi_cuda_cnn_tpu.obs.schema import load_records

    sink = tmp_path / "serve.jsonl"
    rc = serve_bench_main([
        "--requests", "6", "--dim", "32", "--depth", "1", "--heads", "2",
        "--vocab", "64", "--max-seq", "128", "--prompt-min", "4",
        "--prompt-max", "12", "--out-min", "4", "--out-max", "12",
        "--slots", "2", "--page-size", "8", "--prefill-chunk", "8",
        "--metrics-jsonl", str(sink),
    ])
    assert rc == 0
    recs = load_records(sink, strict=True)
    assert sum(r["event"] == "request" for r in recs) == 12  # 6 x 2 modes
    assert sum(r["event"] == "serve" for r in recs) == 2
    modes = {json.dumps(sorted(r["mode"] for r in recs
                               if r["event"] == "serve"))}
    assert modes == {json.dumps(["continuous", "static"])}


def test_paged_decode_block_rejects_out_of_range_positions():
    """Concrete positions past the block-table extent must raise like
    the contiguous path — past the table the gathered page index would
    clamp to the last column and silently scatter over the sequence's
    final legitimate cache rows."""
    from mpi_cuda_cnn_tpu.models.generate import decode_block

    params = MODEL.init(jax.random.key(0))
    pc = _identity_paged_cache(MODEL, 1, page_size=8)  # covers max_seq=48
    toks = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    with pytest.raises(ValueError, match="out of range"):
        decode_block(MODEL, params, toks, MODEL.max_seq - 2, pc)
    with pytest.raises(ValueError, match="out of range"):
        decode_block(MODEL, params, toks,
                     np.asarray([MODEL.max_seq - 1]), pc)


def test_scheduler_and_engine_rejections():
    params = MODEL.init(jax.random.key(0))
    with pytest.raises(ValueError, match="max_len"):
        sched = ContinuousScheduler(slots=1, pool=PagePool(4), page_size=4,
                                    max_len=16)
        sched.submit([Request(rid=0, prompt=np.zeros(10, np.int32),
                              max_new_tokens=10)])
    with pytest.raises(ValueError, match="empty prompt"):
        Request(rid=0, prompt=np.zeros(0, np.int32), max_new_tokens=1)
    with pytest.raises(ValueError, match="num_pages"):
        PagePool(1)
    # A prompt alone needing more pages than the pool owns could only
    # ever preempt-loop: rejected AT SUBMISSION with a clear error
    # (ISSUE 4 satellite), not discovered as an idle-engine stall.
    engine = PagedEngine(MODEL, params, slots=1, num_pages=2, page_size=4,
                         max_len=16)
    with pytest.raises(ValueError, match="never be admitted"):
        engine.run([Request(rid=0, prompt=np.zeros(8, np.int32),
                            max_new_tokens=4)], mode="continuous")


# -- the per-iteration pool check (Scheduler.check_changed) ---------------


class _Device:
    """ServeCore's compute, stood in for: a chunk writes what the
    scheduler asked, every token is 1, a copy-on-write copies nothing."""

    def prefill_chunk(self, slot):
        return min(4, slot.target - slot.cached), 1

    def decode(self, dslots):
        return {s.idx: 1 for s in dslots}

    def copy_page(self, src, dst):
        pass


# A pool too small for three slots' longest requests (preemption), with
# a prefix cache (hits, copy-on-write, inserts, reclaim) or a windowed
# layer group (pages taken and given back behind the window).
STORMS = {"prefix": dict(prefix=True), "window": dict(window=(8, 4))}


def _storm(kind, seed):
    """A scheduler of `kind` and a `step(t)` that drives it through one
    ServeCore iteration of a seeded mix: arrivals that share a template
    prefix or not, cancellations, prefix reclaim; admission, chunks,
    growth, preemption and finishes are the scheduler's own."""
    rng = np.random.default_rng(seed)
    sched = build_scheduler(mode="continuous", slots=3, num_pages=14,
                            page_size=4, max_len=48, **STORMS[kind])
    core = ServeCore(_Device(), sched)
    tmpl = rng.integers(0, 13, 24).astype(np.int32)
    rids = itertools.count()

    def step(t):
        if rng.random() < 0.4:
            n = int(rng.integers(3, 25))
            prompt = (tmpl[:n].copy() if rng.random() < 0.7
                      else rng.integers(0, 13, n).astype(np.int32))
            core.submit(Request(rid=next(rids), prompt=prompt,
                                max_new_tokens=int(rng.integers(2, 25)),
                                arrival=float(t)))
        busy = [s for s in sched.slots if not s.free]
        if busy and rng.random() < 0.05:
            busy[int(rng.integers(len(busy)))].req.cancel()
        if sched.prefix is not None and rng.random() < 0.15:
            sched.prefix.reclaim(int(rng.integers(1, 4)))
        return core.step(float(t))

    return sched, step


@pytest.mark.parametrize("kind,seed", [(k, s) for k in STORMS
                                       for s in (0, 1, 2)])
def test_the_per_iteration_check_passes_where_the_full_scan_does(kind, seed):
    sched, step = _storm(kind, seed)
    freed = verified = 0
    for t in range(150):
        out = step(t)
        freed += out.window_freed or 0
        sched.check_changed()
        verified += sched.checked[0]
        if t % 3 == 0:
            sched.check()
    sched.check_changed()
    assert sched.checked == (0, 0)     # nothing changed since the last
    sched.check()
    usable = sched.pool.usable + (sched.window.pool.usable
                                  if sched.window is not None else 0)
    assert sched.checked == (usable, 3)
    # The storm took every path, and the check verified what it touched.
    assert sched.preemptions > 0 and sched.finished and sched.dropped
    if kind == "prefix":
        stats = sched.prefix.stats
        assert stats["hits"] and stats["cow_copies"] and stats["evictions"]
    else:
        assert freed > 10
    assert 0 < verified < 150 * usable / 2


def _leak(sched, mp):
    real = PagePool.free

    def free(self, pages, owner):           # loses one page it freed
        real(self, pages, owner)
        self._free_set.discard(self._free.pop())
    mp.setattr(PagePool, "free", free)
    return True


def _double_book(sched, mp):
    real = PagePool.try_alloc

    def try_alloc(self, n, owner):          # issues a page it keeps free
        got = real(self, n, owner)
        if got:
            self._free.append(got[0])
            self._free_set.add(got[0])
        return got
    mp.setattr(PagePool, "try_alloc", try_alloc)
    return True


def _page_zero(sched, mp):
    real = PagePool.try_alloc

    def try_alloc(self, n, owner):          # issues the scratch page
        got = real(self, n, owner)
        if got:
            p = got[-1]
            del self._owner[p]
            self._free.append(p)
            self._free_set.add(p)
            self._owner[0], got[-1] = owner, 0
        return got
    mp.setattr(PagePool, "try_alloc", try_alloc)
    return True


def _readers_on_unowned(sched, mp):
    shared = [p for p, rl in sched.pool._readers.items() if rl]
    if not shared:
        return False
    real = PagePool.free

    def free(self, pages, owner):           # frees a page with readers
        kept = {p: self._readers.pop(p) for p in pages if p in self._readers}
        real(self, pages, owner)
        self._readers.update(kept)
    mp.setattr(PagePool, "free", free)
    sched.pool.free([shared[0]], sched.pool._owner[shared[0]])
    return True


def _writable_shared(sched, mp):
    private = [p for s in sched.slots if not s.free
               for p in s.pages if p not in s.refs]
    if not private:
        return False
    real = PagePool.share

    def share(self, page, reader):          # shares a writable page
        ro = page in self._ro
        self._ro.add(page)
        real(self, page, reader)
        if not ro:
            self._ro.discard(page)
    mp.setattr(PagePool, "share", share)
    sched.pool.share(private[0], "intruder")
    return True


def _shared_in_writable_region(sched, mp):
    real = _SchedulerBase._bind

    def bind(self, slot, req, pages, now, acq=None):  # forgets the match
        real(self, slot, req, pages, now, acq)
        if slot.refs:
            slot.cached = 0
    mp.setattr(type(sched), "_bind", bind)
    return True


def _cow_onto_shared(sched, mp):
    real = _SchedulerBase._bind

    def bind(self, slot, req, pages, now, acq=None):  # copies onto its src
        real(self, slot, req, pages, now, acq)
        if slot.cow is not None:
            slot.cow = (slot.cow[0], slot.cow[0])
    mp.setattr(type(sched), "_bind", bind)
    return True


def _shared_page_freed_under_its_reader(sched, mp):
    slot = next((s for s in sched.slots if s.refs), None)
    if slot is None:
        return False
    page, pool = slot.refs[0], sched.pool
    for reader in list(pool._readers.get(page, ())):   # every reference
        pool.unshare(page, reader)
    pool.free([page], pool._owner[page])                # and the page
    return True


def _extent_falls_under_shared_pages(sched, mp):
    real = ContinuousScheduler.grow_for_decode

    def grow(self, now=0.0, spec_k=1):      # rewinds a slot it kept
        held = {s.idx: len(s.pages) for s in self.slots}
        survivors = real(self, now, spec_k)
        for s in survivors:
            if s.refs and len(s.pages) == held[s.idx]:
                s.cached = 0
        return survivors
    mp.setattr(ContinuousScheduler, "grow_for_decode", grow)
    return True


def _window_page_changes_hands():
    seen = {}

    def fault(sched, mp):
        # A page of a slot whose table did not change since the iteration
        # before changes hands: only its old owner names what changed.
        now = {s.idx: (id(s.wpages), len(s.wpages), s.wfirst)
               for s in sched.slots if not s.free}
        still = [s for s in sched.slots if not s.free
                 and seen.get(s.idx) == now[s.idx] and any(s.wpages)]
        seen.clear()
        seen.update(now)
        others = {s.req.rid for s in sched.slots if not s.free}
        if not still or len(others) < 2:
            return False
        slot = still[0]
        page = next(p for p in slot.wpages[slot.wfirst:] if p)
        sched.window.pool.adopt(page, slot.req.rid,
                                min(others - {slot.req.rid}))
        return True
    return fault


def _window_table_names_a_free_page(sched, mp):
    real = WindowGroup.advance

    def advance(self, slot, rows):          # enters a page not issued
        n = len(slot.wpages)
        real(self, slot, rows)
        if len(slot.wpages) > n:
            slot.wpages[-1] = self.pool._free[-1]
    mp.setattr(WindowGroup, "advance", advance)
    return True


def _window_start_passes_a_page(sched, mp):
    slot = next((s for s in sched.slots if not s.free and s.wpages[s.wfirst:]
                 and s.wpages[s.wfirst] and any(s.wpages[s.wfirst + 1:])),
                None)
    if slot is None:
        return False
    slot.wfirst += 1        # a slot's own field: its snapshot has it
    return True


def _window_page_issued_to_no_table(sched, mp):
    real = WindowGroup.advance

    def advance(self, slot, rows):          # takes a page it never enters
        real(self, slot, rows)
        if not self.pool.try_alloc(1, slot.req.rid):
            raise RuntimeError("the windowed pool ran dry")
    mp.setattr(WindowGroup, "advance", advance)
    return True


# name -> (a maker of the fault, the storms it applies to)
FAULTS = {
    "leak": (lambda: _leak, STORMS),
    "double_booking": (lambda: _double_book, STORMS),
    "page_zero_in_circulation": (lambda: _page_zero, STORMS),
    "readers_on_an_unowned_page": (lambda: _readers_on_unowned, ["prefix"]),
    "writable_page_shared": (lambda: _writable_shared, STORMS),
    "shared_page_in_the_writable_region": (
        lambda: _shared_in_writable_region, ["prefix"]),
    "cow_destination_shared": (lambda: _cow_onto_shared, ["prefix"]),
    "shared_page_freed_under_its_reader": (
        lambda: _shared_page_freed_under_its_reader, ["prefix"]),
    "written_extent_falls_under_shared_pages": (
        lambda: _extent_falls_under_shared_pages, ["prefix"]),
    "windowed_page_changes_hands": (_window_page_changes_hands, ["window"]),
    "windowed_table_names_a_free_page": (
        lambda: _window_table_names_a_free_page, ["window"]),
    "windowed_page_kept_behind_the_window": (
        lambda: _window_start_passes_a_page, ["window"]),
    "windowed_page_issued_to_no_table": (
        lambda: _window_page_issued_to_no_table, ["window"]),
}


def _first_failure(kind, make_fault, check):
    """The iteration at which `check` first raises, in a storm whose
    fault is armed from iteration 20 on (and the one it fired at)."""
    sched, step = _storm(kind, 0)
    fault = make_fault()
    fired = None
    with pytest.MonkeyPatch.context() as mp:
        for t in range(150):
            step(t)
            if t >= 20 and fired is None and fault(sched, mp):
                fired = t
            try:
                check(sched)
            except AssertionError:
                return fired, t
    return fired, None


@pytest.mark.parametrize("name,kind", [(n, k) for n, (_, kinds) in
                                       FAULTS.items() for k in kinds])
def test_the_per_iteration_check_fails_where_the_full_scan_does(name, kind):
    fault = FAULTS[name][0]
    fired, changed = _first_failure(kind, fault,
                                    _SchedulerBase.check_changed)
    assert (fired, changed) == _first_failure(kind, fault,
                                              _SchedulerBase.check)
    assert changed is not None and changed >= fired


@pytest.mark.parametrize("kind", STORMS)
def test_a_field_written_behind_every_mutator_is_the_full_scans(kind):
    """Where the per-iteration check's reach ends: a page no mutator
    touched is as the last check left it, so a field written behind
    every mutator can escape it; the full scan, the run's end's,
    cannot."""
    sched, step = _storm(kind, 0)
    for t in range(40):
        step(t)
    sched.check_changed()
    page = next(p for s in sched.slots if not s.free
                for p in s.pages if p not in s.refs)
    sched.pool._readers[page] = ["ghost"]
    sched.check_changed()
    assert sched.checked == (0, 0)
    with pytest.raises(AssertionError, match="writable page shared"):
        sched.check()
