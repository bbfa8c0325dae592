"""Failure-aware serving fleet (serve/fleet.py + serve/router.py,
ISSUE 7): deterministic dispatch over N replicas, replica lifecycle
(crash / heartbeat detection / backoff restart / circuit breaking /
elastic join / graceful leave), and exactly-once re-dispatch with
generation-token fencing — all on FakeClock, bitwise-reproducible.

SimCompute makes the proofs sharp: token j of request rid is a pure
32-bit mix of (rid, j, salt, prompt length), so "zero double-generated
tokens" is not a statistical claim — any fence leak would put a
wrong-position token into the authoritative output and break exact
equality with the closed-form expectation."""

import json

import numpy as np
import pytest

from mpi_cuda_cnn_tpu.faults import (
    FakeClock,
    FaultInjector,
    parse_plan,
    validate_plan_sites,
)
from mpi_cuda_cnn_tpu.serve.fleet import (
    Fleet,
    SimCompute,
    make_fleet_workload,
)
from mpi_cuda_cnn_tpu.serve.router import Router, stable_hash

VOCAB = 512


def expected_out(req, *, salt=0, n=None, vocab=VOCAB):
    """SimCompute's closed form: the tokens request `req` must end
    with, independent of which replicas served it or how often it was
    preempted / re-dispatched."""
    n = req.max_new_tokens if n is None else n
    return [
        ((req.rid * 1000003 + j * 2654435761 + salt * 97
          + int(req.prompt.size) * 8191) & 0xFFFFFFFF) % vocab
        for j in range(n)
    ]


def workload(n=300, rate=800.0, seed=0, sessions=0, **kw):
    kw.setdefault("vocab", VOCAB)
    kw.setdefault("prompt_min", 8)
    kw.setdefault("prompt_max", 48)
    kw.setdefault("out_min", 4)
    kw.setdefault("out_max", 32)
    return make_fleet_workload(n=n, rate=rate, seed=seed,
                               sessions=sessions, **kw)


def sim_fleet(*, replicas=4, plan=None, seed=0, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("num_pages", 33)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 96)
    kw.setdefault("check_every", 8)
    return Fleet(
        lambda name: SimCompute(vocab=VOCAB, chunk=16, salt=seed),
        replicas=replicas,
        faults=FaultInjector(plan) if plan else None,
        **kw,
    )


CRASH_PLAN = ("replica_crash@fleet.tick:40?replica=1&zombie_ticks=4;"
              "replica_crash@fleet.tick:120?replica=2;"
              "replica_join@fleet.tick:160")


# ------------------------------------------------- the storm acceptance


def test_storm_all_terminal_and_bitwise_deterministic():
    """The acceptance shape at tier-1 size: a seeded Poisson storm on a
    4-replica fleet with two injected crashes (one a zombie) and one
    elastic join. Every request reaches a terminal status, and two
    identical-seed runs are BITWISE equal in dispatch trace, per-status
    totals, and every authoritative output (the CI storm re-proves this
    at 10^5 requests through `mctpu compare`)."""
    results = []
    for _ in range(2):
        res = sim_fleet(plan=CRASH_PLAN).run(workload())
        assert all(r.terminal for r in res.requests)
        assert res.crashes == 2 and res.joins == 1
        assert res.redispatches > 0
        results.append(res)
    a, b = results
    assert a.dispatch_trace == b.dispatch_trace
    assert a.status_counts() == b.status_counts()
    assert a.outputs() == b.outputs()
    assert a.trace_crc == b.trace_crc
    assert a.ticks == b.ticks


def test_zero_double_generation_under_zombie_crash():
    """The fencing proof: a crashed-but-partitioned replica keeps
    stepping for zombie_ticks after failover, and every commit it
    attempts must be refused. The authoritative output of every
    finished request equals SimCompute's closed form EXACTLY — one
    leaked commit would insert a wrong-position token — and the zombie
    provably attempted commits (fenced_discards > 0)."""
    res = sim_fleet(plan=CRASH_PLAN).run(workload())
    assert res.fenced_discards > 0
    for r in res.finished_requests():
        assert r.out == expected_out(r), f"request {r.rid}"
        assert len(r.out) == r.max_new_tokens


def test_crash_fleet_outputs_equal_crash_free_fleet():
    """Crash-vs-crash-free equivalence: the same seeded workload run on
    an identical fleet WITHOUT faults produces identical outputs for
    every request (re-dispatch recovers the schedule's work without
    corrupting any request, affected or not)."""
    reqs_a, reqs_b = workload(), workload()
    crash = sim_fleet(plan=CRASH_PLAN).run(reqs_a)
    clean = sim_fleet(plan=None).run(reqs_b)
    assert clean.redispatches == 0 and clean.crashes == 0
    outs_crash, outs_clean = crash.outputs(), clean.outputs()
    affected = {rid for (_, rid, _, _, kind) in crash.dispatch_trace
                if kind == "redispatch"}
    assert affected, "the crash plan must strand at least one request"
    for rid in outs_clean:
        assert outs_crash[rid] == outs_clean[rid], f"request {rid}"
    assert crash.status_counts() == clean.status_counts()


def test_redispatch_exactly_once_per_failover():
    """Exactly-once: with a single crash, every stranded request
    appears in the dispatch trace exactly once as a redispatch, and
    the redispatched set is exactly the set the failover harvested
    (replica_log's `dead` event records it)."""
    fleet = sim_fleet(plan="replica_crash@fleet.tick:50?replica=1")
    res = fleet.run(workload())
    redis = [rid for (_, rid, _, _, kind) in res.dispatch_trace
             if kind == "redispatch"]
    assert len(redis) == len(set(redis)), "a request re-dispatched twice"
    dead = [e for e in res.replica_log if e["kind"] == "dead"]
    assert len(dead) == 1
    assert sorted(redis) == dead[0]["stranded"]
    # Fences moved forward: each redispatch carries a higher epoch than
    # the original dispatch of the same rid.
    epochs = {}
    for (_, rid, _, epoch, kind) in res.dispatch_trace:
        if kind == "redispatch":
            assert epoch > epochs[rid]
        epochs[rid] = epoch


def test_discard_redispatch_restarts_from_prompt():
    """redispatch="discard" drops the dead replica's partial output and
    regenerates from the prompt; the final outputs still equal the
    closed form (same tokens, regenerated), and the affected requests
    spend strictly more decode work than under "resume"."""
    plan = "replica_crash@fleet.tick:60?replica=0"
    resume = sim_fleet(plan=plan, redispatch="resume").run(workload())
    discard = sim_fleet(plan=plan, redispatch="discard").run(workload())
    for res in (resume, discard):
        for r in res.finished_requests():
            assert r.out == expected_out(r), f"request {r.rid}"
    assert discard.redispatches == resume.redispatches
    assert discard.decode_ticks + discard.prefill_chunks >= \
        resume.decode_ticks + resume.prefill_chunks


def test_storm_100k_scale():
    """The full 10^5-request acceptance storm (slow; CI runs the same
    shape twice through `mctpu fleet-bench` + `mctpu compare` at 0%
    structural tolerance). Here: all terminal, zero double generation
    at scale."""
    reqs = workload(n=100_000, rate=2000.0)
    plan = ("replica_crash@fleet.tick:4000?replica=1&zombie_ticks=4;"
            "replica_crash@fleet.tick:12000?replica=2;"
            "replica_join@fleet.tick:20000")
    res = sim_fleet(replicas=4, slots=8, plan=plan,
                    check_every=256).run(reqs)
    assert len(res.requests) == 100_000
    assert all(r.terminal for r in res.requests)
    assert res.crashes == 2 and res.joins == 1 and res.redispatches > 0
    for r in res.finished_requests():
        assert r.out == expected_out(r)


# ------------------------------------------------- lifecycle mechanics


def test_heartbeat_detection_lag():
    """A crash is detected by heartbeat staleness, not by the fault:
    the `dead` event lands exactly heartbeat_miss ticks after the
    crash (the replica misses its beat at the crash tick and the next
    miss-1 ticks; the check runs before beats, so missed = lag - 1)."""
    fleet = sim_fleet(plan="replica_crash@fleet.tick:30?replica=1",
                      heartbeat_miss=5)
    res = fleet.run(workload(n=120))
    crash = next(e for e in res.replica_log if e["kind"] == "crash")
    dead = next(e for e in res.replica_log if e["kind"] == "dead")
    assert crash["tick"] == 30
    assert dead["tick"] == 30 + 5


def test_heartbeat_miss_one_never_kills_a_healthy_replica():
    """The tightest legal detector (heartbeat_miss=1) must not declare
    live, beating replicas dead — the staleness check runs before the
    tick's beats, so a healthy member's lag of 1 is zero MISSED beats."""
    res = sim_fleet(replicas=2, heartbeat_miss=1).run(workload(n=60))
    assert {r.status for r in res.requests} == {"finished"}
    assert not any(e["kind"] == "dead" for e in res.replica_log)
    # And it still detects a real crash, one tick after it.
    crashed = sim_fleet(replicas=2, heartbeat_miss=1,
                        plan="replica_crash@fleet.tick:20?replica=1")
    res = crashed.run(workload(n=60))
    dead = next(e for e in res.replica_log if e["kind"] == "dead")
    assert dead["tick"] == 21
    assert {r.status for r in res.requests} == {"finished"}


def test_backoff_restart_rejoins_and_serves():
    """A crashed replica rejoins after utils/retry.backoff_delay and
    receives new dispatches (fresh incarnation, empty pools)."""
    fleet = sim_fleet(plan="replica_crash@fleet.tick:40?replica=1",
                      backoff_base=0.01)
    res = fleet.run(workload())
    kinds = [e["kind"] for e in res.replica_log if e["name"] == "r1"]
    assert kinds == ["crash", "dead", "restart_scheduled", "restart"]
    sched = next(e for e in res.replica_log
                 if e["kind"] == "restart_scheduled")
    assert sched["delay_s"] > 0
    restart_tick = next(e["tick"] for e in res.replica_log
                        if e["kind"] == "restart")
    assert any(name == "r1" and tick >= restart_tick
               for (tick, _, name, _, _) in res.dispatch_trace)
    assert res.replicas_final == 4


def test_circuit_breaker_removes_flapping_replica():
    """A replica that keeps crashing exhausts max_flaps and is
    permanently removed (circuit open) — the fleet keeps serving on
    the survivors and every request still terminates."""
    plan = ("replica_crash@fleet.tick:20?replica=1;"
            "replica_crash@fleet.tick:60?replica=1")
    fleet = sim_fleet(plan=plan, max_flaps=1)
    res = fleet.run(workload())
    assert res.crashes == 2
    assert res.circuit_opens == 1
    assert res.restarts == 1          # only the first crash earned one
    assert res.replicas_final == 3    # r1 never came back
    assert any(e["kind"] == "circuit_open" for e in res.replica_log)
    assert all(r.terminal for r in res.requests)
    assert {r.status for r in res.requests} == {"finished"}


def test_elastic_join_takes_load():
    """replica_join scales out mid-storm: the joined replica appears in
    the dispatch trace after its join tick and the fleet ends larger."""
    fleet = sim_fleet(replicas=2,
                      plan="replica_join@fleet.tick:30?replicas=2")
    res = fleet.run(workload())
    assert res.joins == 2 and res.replicas_final == 4
    joined = {e["name"] for e in res.replica_log if e["kind"] == "join"}
    assert joined == {"r2", "r3"}
    served = {name for (_, _, name, _, _) in res.dispatch_trace}
    assert joined <= served


def test_graceful_leave_drains_without_redispatch():
    """replica_leave stops new dispatches immediately but the leaving
    replica finishes its in-flight work — a drain is not a failover, so
    nothing is re-dispatched and nothing is lost."""
    fleet = sim_fleet(replicas=3,
                      plan="replica_leave@fleet.tick:50?replica=1")
    res = fleet.run(workload())
    assert res.leaves == 1 and res.redispatches == 0
    assert res.replicas_final == 2
    drain = next(e for e in res.replica_log
                 if e["kind"] == "drain_complete")
    leave = next(e for e in res.replica_log if e["kind"] == "leave")
    assert drain["tick"] >= leave["tick"]
    assert not any(name == "r1" and tick > leave["tick"]
                   for (tick, _, name, _, _) in res.dispatch_trace)
    assert {r.status for r in res.requests} == {"finished"}


def test_empty_fleet_waits_for_a_scheduled_join():
    """Losing every replica is not a dead end while the fault plan
    still schedules a replica_join: the fleet ticks through the gap
    and the joined replica serves everything — requests are failed
    terminally only when NO capacity can ever arrive."""
    plan = ("replica_crash@fleet.tick:5?replica=0;"
            "replica_join@fleet.tick:60")
    res = sim_fleet(replicas=1, max_flaps=0, plan=plan).run(workload(n=40))
    assert res.replicas_final == 1 and res.joins == 1
    assert {r.status for r in res.requests} == {"finished"}


def test_all_replicas_lost_fails_remaining_terminally():
    """Losing every replica with the breaker open must still land every
    request in a terminal status — the stranded remainder fails with an
    explicit reason instead of hanging the loop."""
    plan = ("replica_crash@fleet.tick:10?replica=0;"
            "replica_crash@fleet.tick:10?replica=1")
    fleet = sim_fleet(replicas=2, max_flaps=0, plan=plan)
    res = fleet.run(workload(n=80))
    assert res.replicas_final == 0 and res.circuit_opens == 2
    assert all(r.terminal for r in res.requests)
    failed = [r for r in res.requests if r.status == "failed"]
    assert failed and all(r.fail_reason == "fleet has no replicas"
                          for r in failed)
    # A future arrival fails AT its arrival, never before it: a
    # finished_at earlier than arrival would emit negative latency_ms
    # into the obs request records.
    assert all(r.finished_at >= r.arrival for r in failed)


def test_fleet_cancel_reaches_the_holding_replica():
    """Fleet.cancel(rid) lands on BOTH the authoritative request and
    the replica-local copy in flight (distinct objects), fleet-wide:
    the request leaves with status 'cancelled' and fewer tokens than
    its budget. Invoked mid-run from the fleet sink (the loop calls
    sinks every tick), the way a client-side abort arrives."""
    reqs = workload(n=40)
    fleet = sim_fleet(replicas=2)

    def sink(rec):
        if rec["tick"] == 5:
            fleet.cancel(reqs[0].rid)
            fleet.cancel(10**9)  # unknown rid: no-op, no raise
    fleet.fleet_sink = sink
    res = fleet.run(reqs)
    assert all(r.terminal for r in res.requests)
    victim = next(r for r in res.requests if r.rid == reqs[0].rid)
    assert victim.status == "cancelled"
    assert len(victim.out) < victim.max_new_tokens
    assert sum(1 for r in res.requests if r.status == "cancelled") == 1


def test_draining_replica_crash_completes_the_leave():
    """A replica asked to leave that then crashes must NOT be
    restarted: the crash completes the departure (its in-flight work
    fails over normally), instead of the backoff restart resurrecting
    it as a dispatch-taking member against the operator's intent."""
    plan = ("replica_leave@fleet.tick:20?replica=1;"
            "replica_crash@fleet.tick:40?replica=1")
    res = sim_fleet(replicas=3, plan=plan).run(workload())
    assert res.leaves == 1 and res.crashes == 1
    assert res.restarts == 0 and res.replicas_final == 2
    kinds = [e["kind"] for e in res.replica_log if e["name"] == "r1"]
    assert kinds == ["leave", "crash", "dead"]
    dead = next(e for e in res.replica_log if e["kind"] == "dead")
    assert dead.get("draining") is True
    assert all(r.terminal for r in res.requests)
    assert not any(name == "r1" and kind == "redispatch"
                   for (_, _, name, _, kind) in res.dispatch_trace)


# ------------------------------------------------- dispatch policies


def test_session_affinity_keeps_sessions_on_one_replica():
    """The session policy rendezvous-hashes each session onto one
    replica: every dispatch of a session lands on the same member, and
    a crash moves ONLY the dead replica's sessions."""
    reqs = workload(n=200, sessions=12)
    res = sim_fleet(policy="session").run(workload(n=200, sessions=12))
    by_session = {}
    rid_session = {r.rid: r.session for r in reqs}
    for (_, rid, name, _, kind) in res.dispatch_trace:
        assert kind == "dispatch"
        by_session.setdefault(rid_session[rid], set()).add(name)
    assert all(len(names) == 1 for names in by_session.values())
    assert len(set().union(*by_session.values())) > 1

    crashed = sim_fleet(policy="session",
                        plan="replica_crash@fleet.tick:40?replica=1",
                        max_flaps=0).run(workload(n=200, sessions=12))
    home = {s: next(iter(n)) for s, n in by_session.items()}
    for (_tick, rid, name, _, _kind) in crashed.dispatch_trace:
        s = rid_session[rid]
        if home[s] != "r1":
            # Sessions not homed on the dead replica never move.
            assert name == home[s], f"session {s} moved to {name}"


def test_least_loaded_spreads_a_burst():
    """Least-loaded dispatch reads the per-replica telemetry gauges
    plus same-tick pending dispatches, so a burst arriving within one
    tick spreads across the fleet instead of dog-piling one replica."""
    res = sim_fleet(replicas=4).run(workload(n=64, rate=0.0))
    first_tick = [name for (tick, _, name, _, _) in res.dispatch_trace
                  if tick == 0]
    assert len(set(first_tick)) == 4


def test_rendezvous_hash_is_process_stable():
    """stable_hash must not depend on Python's randomized str hash —
    pin a few values so a restart cannot unseat every session."""
    assert stable_hash("s", "r0") == stable_hash("s", "r0")
    assert stable_hash(7, "r1") != stable_hash(7, "r2")
    # Golden values: process-independence means these never drift.
    assert stable_hash("session-a", "r0") == 1166997687
    assert stable_hash(0, "r1") == 1570464646


def test_router_rejects_bad_config():
    with pytest.raises(ValueError, match="policy"):
        Router("round_robin")
    with pytest.raises(ValueError, match="heartbeat_miss"):
        Router(heartbeat_miss=0)
    with pytest.raises(ValueError, match="at least one replica"):
        sim_fleet(replicas=0)
    with pytest.raises(ValueError, match="redispatch"):
        sim_fleet(redispatch="retry")


def test_fleet_rejects_structurally_impossible_requests():
    """Admission-impossible requests die at run() entry with a clear
    error, fleet-wide, before any replica sees them."""
    fleet = sim_fleet()
    bad = workload(n=4)
    bad[2].max_new_tokens = 200  # prompt + new > max_len 96
    with pytest.raises(ValueError, match="exceeds max_len"):
        fleet.run(bad)


# ------------------------------------------------- fault-plan surface


def test_replica_fault_sites_validate_per_surface():
    """`replica_crash@serve.tick` on plain serve-bench (or any site the
    chosen subcommand never registers) errors at validation time
    instead of silently never firing."""
    plan = parse_plan("replica_crash@fleet.tick:10?replica=1")
    validate_plan_sites(plan, "fleet-bench")  # ok
    with pytest.raises(ValueError, match="never reached"):
        validate_plan_sites(plan, "serve-bench")
    with pytest.raises(ValueError, match="never reached"):
        validate_plan_sites("slow@serve.tick:3?s=0.1", "fleet-bench")
    with pytest.raises(ValueError, match="never reached"):
        validate_plan_sites("crash@train.step:2", "serve-bench")
    validate_plan_sites("crash@train.step:2", "train")
    # Kinds are validated per site too: a legal site with a kind its
    # consumer ignores would fire and silently do nothing.
    with pytest.raises(ValueError, match="never applied"):
        validate_plan_sites("replica_crash@train.step:2", "train")
    with pytest.raises(ValueError, match="never applied"):
        validate_plan_sites("nan@serve.tick:3", "serve-bench")
    with pytest.raises(ValueError, match="never applied"):
        validate_plan_sites("squeeze@fleet.tick:3?pages=2&ticks=2",
                            "fleet-bench")
    validate_plan_sites("nan@train.batch:1;preempt@train.step:9", "train")
    validate_plan_sites("squeeze@serve.tick:2?pages=2&ticks=3",
                        "serve-bench")
    # The LM trainer has no train.batch hook: nan@train.batch is valid
    # on the CNN surface but must error on train-lm (it would validate
    # then silently never fire — the exact hole this closes).
    with pytest.raises(ValueError, match="never reached"):
        validate_plan_sites("nan@train.batch:3", "train-lm")
    validate_plan_sites("preempt@train.step:9;crash@ckpt.manifest:1",
                        "train-lm")


def test_redispatch_is_never_backpressure_rejected():
    """A harvested request re-dispatched after a crash keeps its
    first-admission mark, so the surviving replica's queue bound
    (enforce_queue_bound exempts admitted_at-bearing requests) treats
    it as in-flight work, never as a fresh arrival it may reject —
    dropping tokens the fleet already served would break the
    exactly-once contract."""
    fleet = sim_fleet(replicas=2, max_queue=2,
                      plan="replica_crash@fleet.tick:6?replica=1")
    res = fleet.run(workload(n=40, rate=4000.0))
    assert res.crashes == 1 and res.redispatches > 0
    served_then_rejected = [
        r for r in res.requests if r.status == "rejected" and r.out
    ]
    assert not served_then_rejected, served_then_rejected
    # A re-dispatched rid that was merely QUEUED on the dead replica is
    # a fresh arrival at the survivor and may be backpressure-rejected;
    # one that was admitted (it has committed tokens) must finish. The
    # storm must actually exercise that case for this test to bite.
    redispatched = {rid for _, rid, _, _, kind in res.dispatch_trace
                    if kind == "redispatch"}
    finished = {r.rid for r in res.requests if r.status == "finished"}
    assert redispatched & finished, "no re-dispatched request finished"


def test_crash_fault_naming_unknown_replica_errors_loudly():
    """A crash/leave fault naming a replica that has NEVER joined the
    fleet (e.g. replica=7 on a 4-replica run) raises at fire time
    instead of silently never firing — the same contract argparse-time
    site validation pins, extended to the target: a resilience run must
    never report crashes=0 because of a typo'd index."""
    fleet = sim_fleet(plan="replica_crash@fleet.tick:10?replica=7")
    with pytest.raises(ValueError, match="never joined"):
        fleet.run(workload(n=8))
    fleet = sim_fleet(plan="replica_leave@fleet.tick:10?replica=9")
    with pytest.raises(ValueError, match="never joined"):
        fleet.run(workload(n=8))


def test_fleet_bench_cli_rejects_wrong_site():
    from mpi_cuda_cnn_tpu.serve.bench import fleet_bench_main

    with pytest.raises(SystemExit) as exc:
        fleet_bench_main(["--fault-plan", "slow@serve.tick:3?s=0.1"])
    assert exc.value.code == 2


# ------------------------------------------------- obs + CLI round trip


def test_fleet_bench_cli_e2e_trace_and_compare(tmp_path):
    """`mctpu fleet-bench` -> `mctpu trace` -> `mctpu compare` round
    trip: the run's telemetry reconstructs every request consistently
    across the re-dispatch, and two identical-seed runs pass the CI
    fleet gate (exact structural equality) while a different-seed run
    fails it."""
    import os

    from mpi_cuda_cnn_tpu.obs.regress import compare_main
    from mpi_cuda_cnn_tpu.obs.timeline import trace_main
    from mpi_cuda_cnn_tpu.serve.bench import fleet_bench_main

    args = ["--replicas", "3", "--requests", "80", "--rate", "500",
            "--fault-plan",
            "replica_crash@fleet.tick:30?replica=1&zombie_ticks=2",
            "--seed", "3"]
    runs = []
    for tag in ("a", "b"):
        path = str(tmp_path / f"fleet_{tag}.jsonl")
        assert fleet_bench_main([*args, "--metrics-jsonl", path]) == 0
        runs.append(path)
    assert trace_main([runs[0]]) == 0

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gate = os.path.join(repo, "ci", "fleet_gate.json")
    assert compare_main([*runs, "--gate", gate]) == 0

    drifted = str(tmp_path / "fleet_c.jsonl")
    assert fleet_bench_main(["--replicas", "3", "--requests", "80",
                             "--rate", "500", "--seed", "4",
                             "--metrics-jsonl", drifted]) == 0
    assert compare_main([runs[0], drifted, "--gate", gate]) == 1


def test_fleet_metrics_registry_and_sinks():
    """Telemetry opt-in: registry counters agree with the result's
    structural counts, the fleet sink sees every tick, and the
    replica tick sink's modes cover every incarnation that stepped."""
    from mpi_cuda_cnn_tpu.obs.metrics import MetricsRegistry

    from mpi_cuda_cnn_tpu.faults import FakeClock

    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    fleet_recs, tick_recs = [], []
    fleet = Fleet(
        lambda name: SimCompute(vocab=VOCAB, chunk=16, salt=0),
        replicas=3, slots=4, num_pages=33, page_size=8, max_len=96,
        faults=FaultInjector("replica_crash@fleet.tick:25?replica=0"),
        clock=clock, registry=reg,
        fleet_sink=fleet_recs.append, replica_tick_sink=tick_recs.append,
    )
    res = fleet.run(workload(n=120))
    assert len(fleet_recs) == res.ticks
    assert reg.counters["fleet.dispatches"].value == res.dispatches
    assert reg.counters["fleet.redispatches"].value == res.redispatches
    assert reg.counters["fleet.replica_crash"].value == 1
    modes = {r["mode"] for r in tick_recs}
    assert {"fleet/r0", "fleet/r1", "fleet/r2"} <= modes
    # Per-status totals seen by the registry match the result.
    fin = reg.counters.get("serve.requests_finished")
    assert fin is not None
    assert fin.value == res.status_counts()["finished"]


def test_fleet_summary_is_json_serializable():
    res = sim_fleet(plan=CRASH_PLAN).run(workload(n=100))
    s = json.loads(json.dumps(res.summary()))
    assert s["mode"] == "fleet"
    assert s["requests"] == 100
    assert s["dispatches"] == 100
    assert s["crashes"] == 2
    recs = res.request_records()
    assert len(recs) == 100 and all(r["mode"] == "fleet" for r in recs)


# ------------------------------------------------- engine-backed fleet


@pytest.mark.parametrize("prefix,spec", [(False, "off"), (True, "off"),
                                         (False, "lookup")],
                         ids=["plain", "prefix", "spec-lookup"])
def test_single_replica_fleet_matches_paged_engine_run(prefix, spec):
    """PagedEngine.run and the fleet's Replica.step drive ONE iteration
    (serve/core.py's ServeCore); what still differs is the driver —
    run()'s wall clock, arrival-driven sleeps and every-iteration sweep
    against the fleet's stepped clock, router and fenced commit. This
    pins the two drivers against each other: the same workload through
    PagedEngine.run and through a 1-replica engine-backed fleet must
    finish every request with identical outputs, statuses, and
    prefill-chunk counts — with prefix sharing and with speculative
    rounds as without."""
    import jax

    from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
    from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
    from mpi_cuda_cnn_tpu.serve.fleet import EngineCompute

    model = TransformerLM(vocab=13, dim=32, heads=4, depth=2, max_seq=48)
    params = model.init(jax.random.key(0))
    geom = dict(slots=2, num_pages=13, page_size=8, max_len=48)
    ekw = dict(prefill_chunk=8, spec=spec, spec_k=4, **geom)

    def reqs():
        return make_fleet_workload(n=12, vocab=13, prompt_min=4,
                                   prompt_max=10, out_min=4, out_max=10,
                                   rate=300.0, seed=3,
                                   prefix_mix=0.7 if prefix else 0.0)

    engine = PagedEngine(model, params, **ekw)
    clock = FakeClock()
    eng = engine.run(reqs(), mode="continuous", time_fn=clock,
                     sleep_fn=clock.advance, prefix=prefix,
                     spec=spec != "off")
    fleet = Fleet(
        lambda name: EngineCompute(PagedEngine(model, params, **ekw)),
        replicas=1, prefix=prefix, spec=spec, spec_k=4, **geom,
    ).run(reqs())

    assert {r.status for r in eng.requests} == {"finished"}
    assert fleet.status_counts() == {"finished": 12}
    eng_outs = {r.rid: list(r.out) for r in eng.requests}
    assert fleet.outputs() == eng_outs
    # Chunk counts are per-request structure (ceil(uncached prompt /
    # chunk) each) and must agree; decode TICK counts are batching
    # density — a function of admission cadence (fleet tick clock vs
    # engine.run's arrival-driven sleeps), legitimately different
    # between drivers.
    assert fleet.prefill_chunks == eng.prefill_chunks
    if prefix:
        assert eng.prefix["prefix_hits"] > 0
        assert fleet.prefix["prefix_hits"] == eng.prefix["prefix_hits"]
    if spec != "off":
        assert eng.spec["spec_rounds"] > 0 and fleet.spec["spec_rounds"] > 0


@pytest.mark.parametrize("redispatch", ["resume", "discard"])
def test_engine_fleet_crash_outputs_match_crash_free(redispatch):
    """The model-backed fleet (one PagedEngine per replica, shared
    weights): a crash mid-storm re-dispatches in-flight requests to the
    surviving replica, and every finished output is BITWISE equal to
    the crash-free fleet's — cross-replica resume re-prefills prompt +
    committed tokens through the same jitted programs (the PR-3
    recompute-preemption parity, now across replicas)."""
    import jax

    from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
    from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
    from mpi_cuda_cnn_tpu.serve.fleet import EngineCompute

    model = TransformerLM(vocab=13, dim=32, heads=4, depth=2, max_seq=48)
    params = model.init(jax.random.key(0))

    def factory(name):
        return EngineCompute(PagedEngine(
            model, params, slots=2, num_pages=13, page_size=8,
            prefill_chunk=8, max_len=48,
        ))

    def build(plan):
        # max_flaps=0: the crashed replica never rejoins, so the test
        # compiles three engine incarnations instead of four.
        return Fleet(factory, replicas=2, slots=2, num_pages=13,
                     page_size=8, max_len=48, redispatch=redispatch,
                     max_flaps=0,
                     faults=FaultInjector(plan) if plan else None)

    def reqs():
        return make_fleet_workload(n=10, vocab=13, prompt_min=4,
                                   prompt_max=10, out_min=4, out_max=10,
                                   rate=300.0, seed=1)

    crash = build("replica_crash@fleet.tick:8?replica=0").run(reqs())
    clean = build(None).run(reqs())
    assert crash.crashes == 1
    assert crash.status_counts() == clean.status_counts()
    assert {r.status for r in clean.requests} == {"finished"}
    outs_crash, outs_clean = crash.outputs(), clean.outputs()
    for rid, out in outs_clean.items():
        assert outs_crash[rid] == out, f"request {rid}"


# ------------------------------------------------- lossy transport (ISSUE 20)


def transport_fleet(*, replicas=4, plan=None, seed=0, **kw):
    kw.setdefault("transport", True)
    return sim_fleet(replicas=replicas, plan=plan, seed=seed, **kw)


def test_transport_zero_fault_bus_matches_direct_fleet_bitwise():
    """The parity acceptance: with zero transport faults the bus-routed
    fleet is BITWISE-equal to the direct-call fleet per request —
    dispatch trace, statuses, every authoritative output, tick count.
    Zero-fault delivery is inline (send() invokes the handler
    synchronously), so this holds by construction, and the wire
    counters prove no message ever queued. state_crc legitimately
    differs (the bus folds its digest as a 6th component); trace_crc
    is the request-level criterion."""
    direct = sim_fleet().run(workload())
    bus = transport_fleet().run(workload())
    assert bus.dispatch_trace == direct.dispatch_trace
    assert bus.status_counts() == direct.status_counts()
    assert bus.outputs() == direct.outputs()
    assert bus.trace_crc == direct.trace_crc
    assert bus.ticks == direct.ticks
    s = bus.summary()
    assert s["msgs_sent"] > 0 and s["msgs_sent"] == s["msgs_delivered"]
    for k in ("msgs_dropped", "msgs_duped", "msgs_delayed",
              "msgs_deduped", "retransmits", "lease_refusals",
              "partitions"):
        assert s[k] == 0, k
    # Direct-mode summaries carry the same keys, pinned to zero.
    assert all(direct.summary()[k] == 0 for k in
               ("msgs_sent", "retransmits", "lease_refusals"))


PARTITION_PLAN = (
    "msg_delay@fleet.transport:10?count=4&ticks=5&kind=dispatch;"
    "partition@fleet.transport:30?replica=1&ticks=12;"
    "msg_dup@fleet.transport:60?count=2;"
    "msg_drop@fleet.transport:70?count=3&kind=commit;"
    "replica_crash@fleet.tick:90?replica=2&zombie_ticks=3")


def test_partition_false_positive_death_heals_exactly_once():
    """The partition e2e at tier-1 scale: a 12-tick window isolates a
    LIVE replica (heartbeat_miss=3, so the router declares it dead —
    failure detection is fallible, late is not dead), its in-flight
    work is re-dispatched, the isolated replica keeps serving into the
    void until its lease expires and then REFUSES its own commits, and
    on heal every stale commit is lease/fence-refused: every request
    terminal exactly once, every finished output token-for-token equal
    to the SimCompute closed form, zero double generation. Composed
    with message delay / dup / drop and a real zombie crash so the
    false-positive path is proven against the true-positive one."""
    results = []
    for _ in range(2):
        res = transport_fleet(plan=PARTITION_PLAN).run(workload())
        results.append(res)
    a, b = results
    assert all(r.terminal for r in a.requests)
    assert len(a.requests) == 300
    for r in a.finished_requests():
        assert r.out == expected_out(r)
    # The false positive really happened: r1 was declared dead (and
    # torn down / restarted) without ever crashing...
    r1 = [e["kind"] for e in a.replica_log if e.get("name") == "r1"]
    assert "dead" in r1 and "crash" not in r1
    # ...while it was ISOLATED, not gone — and its post-lease commit
    # attempts were refused, which is the zero-double-generation
    # mechanism under partitions.
    assert "isolated" in r1 and "isolated_end" in r1
    assert a.lease_refusals > 0
    assert a.redispatches > 0
    # Partition lifecycle reached the transport log (open then heal).
    kinds = [e["kind"] for e in a.transport_log]
    assert kinds.count("partition_open") == 1
    assert kinds.count("partition_heal") == 1
    # Wire accounting: messages really dropped (partition + msg_drop),
    # duplicated (msg_dup), delayed (msg_delay), deduplicated, and
    # retransmitted — with conservation at quiesce.
    s = a.summary()
    for k in ("msgs_dropped", "msgs_duped", "msgs_delayed",
              "msgs_deduped", "retransmits"):
        assert s[k] > 0, k
    assert s["partitions"] == 1
    assert (s["msgs_sent"] == s["msgs_delivered"] + s["msgs_deduped"]
            + s["msgs_dropped"])
    # The true-positive leg still holds alongside.
    assert a.crashes == 1
    # Bitwise determinism across the identical-seed twin.
    assert a.dispatch_trace == b.dispatch_trace
    assert a.status_counts() == b.status_counts()
    assert a.outputs() == b.outputs()
    assert a.trace_crc == b.trace_crc
    assert a.summary()["state_crc"] == b.summary()["state_crc"]
    assert a.lease_refusals == b.lease_refusals


def test_transport_storm_100k_partition_scale():
    """The full 10^5-request transport acceptance storm (slow; CI runs
    the same shape twice through `mctpu fleet-bench --transport` +
    `mctpu compare` at 0% tolerance): one partition + heal isolating a
    live replica, one false-positive death, one zombie crash — all
    terminal exactly once, zero double generation at scale."""
    reqs = workload(n=100_000, rate=2000.0)
    plan = ("partition@fleet.transport:4000?replica=1&ticks=12;"
            "msg_dup@fleet.transport:12000?count=3;"
            "replica_crash@fleet.tick:20000?replica=2&zombie_ticks=4")
    res = transport_fleet(replicas=4, slots=8, plan=plan,
                          check_every=256).run(reqs)
    assert len(res.requests) == 100_000
    assert all(r.terminal for r in res.requests)
    for r in res.finished_requests():
        assert r.out == expected_out(r)
    assert res.lease_refusals > 0
    assert res.crashes == 1 and res.partitions == 1
    assert res.redispatches > 0
    s = res.summary()
    assert (s["msgs_sent"] == s["msgs_delivered"] + s["msgs_deduped"]
            + s["msgs_dropped"])


def test_heartbeat_detection_is_derived_from_message_loss():
    """The heartbeat bugfix satellite: under the bus, `dead` is a
    DERIVED effect of heartbeat messages not arriving — not a
    privileged side channel. (a) A real crash is detected with exactly
    the same lag as the direct-call fleet (the back-compat the
    existing detection-lag test pins); (b) dropping ONLY r1's hb
    messages on the wire produces a false-positive death of a healthy
    replica — pure message loss, no fault at the replica — and the
    run still ends exactly-once with closed-form outputs."""
    miss = 5
    fleet = transport_fleet(
        plan="replica_crash@fleet.tick:30?replica=1", heartbeat_miss=miss)
    res = fleet.run(workload(n=120))
    crash = next(e for e in res.replica_log if e["kind"] == "crash")
    dead = next(e for e in res.replica_log if e["kind"] == "dead")
    assert crash["tick"] == 30
    assert dead["tick"] == 30 + miss
    # (b) targeted drop: enough consecutive hb losses to cross the
    # staleness window kill a replica that never stopped working.
    lossy = transport_fleet(
        plan="msg_drop@fleet.transport:30?kind=hb&replica=1&count=10",
        heartbeat_miss=3)
    res = lossy.run(workload(n=200))
    r1 = [e["kind"] for e in res.replica_log if e.get("name") == "r1"]
    assert "dead" in r1 and "crash" not in r1 and "isolated" in r1
    assert all(r.terminal for r in res.requests)
    for r in res.finished_requests():
        assert r.out == expected_out(r)
    assert res.summary()["msgs_dropped"] >= 10
