"""`mctpu serve-bench` / `mctpu fleet-bench` — serving benchmarks.

Drives the PagedEngine with a Poisson-arrival workload of mixed
prompt/output lengths (the serving regime the schedulers differ on:
identical lengths make static batching look fine) and reports, per
mode: throughput, TTFT p50/p99, per-output-token latency p50/p99,
decode-tick and preemption counts. Per-request records go through the
obs JSONL schema (`request` events + one `serve` summary event per
mode) so `mctpu report` renders the serving tables.

The workload is seeded and regenerated identically per mode — the two
schedulers see the same requests, arrivals, and (greedy) token budget;
only the schedule differs. Weights are randomly initialized: scheduling
costs do not depend on what the tokens say.

    python -m mpi_cuda_cnn_tpu serve-bench --requests 32 --rate 50
    python scripts/bench_serve.py --mode continuous --cache-dtype int8

`fleet-bench` (ISSUE 7) drives serve/fleet.py instead: N replicas
behind the router on one FakeClock, a seeded Poisson storm, optional
injected replica crashes/joins/leaves — the determinism acceptance
(two identical-seed runs bitwise-equal in dispatch trace and
per-status counts) is what CI's fleet gate compares.

    python -m mpi_cuda_cnn_tpu fleet-bench --replicas 4 --requests 1000
    python scripts/bench_fleet.py --fault-plan \
        'replica_crash@fleet.tick:40?replica=1'
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def _fault_plan_arg(surface: str):
    """--fault-plan argparse type: grammar + hook-site/kind validation
    at parse time (ISSUE 7 satellite) — `replica_crash@fleet.tick` on
    plain serve-bench would silently never fire; it errors here."""
    from ..faults import fault_plan_arg

    return fault_plan_arg(surface)


def _heavy_tail_len(lrng, lo: int, hi: int) -> int:
    """One lognormal length draw clipped to [lo, hi]: median at the
    geometric midpoint, sigma a quarter of the log-range — most mass
    near the low end with a heavy tail that piles up at the clip, the
    production shape (Splitwise) uniform mixes miss."""
    if hi <= lo:
        return lo
    mu = 0.5 * (np.log(lo) + np.log(hi))
    sigma = (np.log(hi) - np.log(lo)) / 4.0
    v = int(round(float(lrng.lognormal(mu, sigma))))
    return min(max(v, lo), hi)


def make_workload(*, n: int, vocab: int, prompt_min: int, prompt_max: int,
                  out_min: int, out_max: int, rate: float, seed: int,
                  deadline_s: float = 0.0, tenants: int = 0,
                  prefix_mix: float = 0.0, prefix_pool: int = 4,
                  len_dist: str = "uniform", templates: int = 0):
    """n seeded requests: uniform prompt/output lengths in the given
    ranges, Poisson arrivals at `rate` req/s (exponential gaps; rate 0
    = everything arrives at t=0). deadline_s > 0 gives every request an
    absolute deadline of arrival + deadline_s. Regenerating with the
    same seed gives an identical workload — the cross-mode comparison
    contract.

    len_dist "lognormal" (ROADMAP item 4 / ISSUE 16) draws prompt and
    output lengths from a heavy-tail lognormal clipped to the same
    ranges instead of uniform. The draws come from a SEPARATE (seed, 3)
    spawn — the same isolation trick the tenant/prefix streams use —
    so the default uniform stream is bitwise-unchanged (every committed
    baseline and pinned CRC stays valid), and tenant labels stay
    identical across the two mixes (the tenant stream never moves).

    tenants > 0 tags each request with a seeded tenant draw over
    "t0".."t{tenants-1}" (ISSUE 8's multi-tenant traffic mix). The
    labels come from a SEPARATE generator ((seed, 1) spawn), so the
    prompt/length/arrival stream is bitwise-identical with tagging on
    or off — committed baselines and every pinned tick count stay
    valid, and the same seed always maps request i to the same tenant.

    prefix_mix > 0 (ISSUE 9) makes that fraction of requests share
    template prefixes: each sharing request's prompt starts with one of
    `prefix_pool` fixed seeded templates, keeping only its last ~1/4 as
    a unique suffix — the system/template-prefix regime prefix sharing
    exists for (varying lengths hit the tree at different depths, so
    COW branching is exercised too). All prefix decisions come from a
    (seed, 2) spawn and OVERWRITE an already-drawn prompt, so lengths,
    arrivals, and tenant labels are bitwise-identical at any mix.

    templates > 0 (ISSUE 17) overrides prefix_pool with an explicitly
    sized template WORKING SET whose content comes from a SEPARATE
    (seed, 4) spawn — the --len-dist precedent again, so the default
    (templates=0) stream is bitwise-unchanged and every pinned workload
    CRC stays valid. Sizing the working set past the device page pool
    is what makes the host-tier spill/readmit story measurable: more
    templates than HBM retains forces LRU reclaim between hits."""
    from .scheduler import Request

    if len_dist not in ("uniform", "lognormal"):
        raise ValueError(f"len_dist {len_dist!r}: want uniform or "
                         "lognormal")
    rng = np.random.default_rng(seed)
    trng = np.random.default_rng([seed, 1])
    prng = np.random.default_rng([seed, 2])
    lrng = (np.random.default_rng([seed, 3])
            if len_dist == "lognormal" else None)
    if templates > 0:
        wrng = np.random.default_rng([seed, 4])
        pool_n = templates
        tmpl_rng = wrng
    else:
        pool_n = prefix_pool
        tmpl_rng = prng
    templates = [tmpl_rng.integers(0, vocab, (prompt_max,)).astype(np.int32)
                 for _ in range(pool_n)] if prefix_mix > 0 else []
    t = 0.0
    reqs = []
    for i in range(n):
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
        if lrng is None:
            plen = int(rng.integers(prompt_min, prompt_max + 1))
            olen = int(rng.integers(out_min, out_max + 1))
        else:
            plen = _heavy_tail_len(lrng, prompt_min, prompt_max)
            olen = _heavy_tail_len(lrng, out_min, out_max)
        prompt = rng.integers(0, vocab, (plen,)).astype(np.int32)
        tenant = (f"t{int(trng.integers(0, tenants))}" if tenants > 0
                  else None)
        if templates and float(prng.random()) < prefix_mix:
            k = int(prng.integers(0, pool_n))
            shared = plen - max(1, plen // 4)
            if shared > 0:
                prompt = np.concatenate(
                    [templates[k][:shared], prompt[shared:]])
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=olen,
                            arrival=t,
                            deadline=t + deadline_s if deadline_s > 0
                            else None, tenant=tenant))
    return reqs


def load_trace(path: str) -> list[dict]:
    """Read the request GEOMETRY out of a finished run's metrics JSONL
    (ROADMAP item 4: trace-driven replay). Every `request` event
    carries the full arrival shape — id, prompt_tokens,
    max_new_tokens, arrival_s, tenant — which is exactly what a
    workload is to a scheduler. Multi-mode runs (serve-bench --mode
    both) record the same regenerated workload once per mode, so the
    FIRST record per id wins; rows come back in arrival order."""
    rows: dict[int, dict] = {}
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"--trace {path}: bad JSONL line: {e}")
            if rec.get("event") != "request":
                continue
            rid = rec.get("id")
            if rid is None or rid in rows:
                continue
            try:
                rows[rid] = {
                    "id": int(rid),
                    "prompt_tokens": int(rec["prompt_tokens"]),
                    "max_new_tokens": int(rec["max_new_tokens"]),
                    "arrival_s": float(rec["arrival_s"]),
                    "tenant": rec.get("tenant"),
                }
            except (KeyError, TypeError, ValueError) as e:
                raise ValueError(
                    f"--trace {path}: request record for id {rid!r} is "
                    f"missing workload geometry ({e})")
    if not rows:
        raise ValueError(f"--trace {path}: no request records — want a "
                         "metrics JSONL from a finished serve-bench / "
                         "fleet-bench run")
    return sorted(rows.values(),
                  key=lambda r: (r["arrival_s"], r["id"]))


def requests_from_trace(rows: list[dict], *, vocab: int, seed: int,
                        deadline_s: float = 0.0):
    """Fresh Request objects from trace geometry — called once per
    mode, like make_workload, because the schedulers consume requests
    in place. Arrival times, token budgets, ids, and tenant labels are
    the recorded ones bit-for-bit; prompt CONTENT is synthesized per
    id from its own seeded spawn (records do not carry tokens), so the
    replay reproduces scheduling pressure, not token identity."""
    from .scheduler import Request

    reqs = []
    for row in rows:
        rng = np.random.default_rng([seed, 5, row["id"]])
        prompt = rng.integers(0, vocab,
                              (row["prompt_tokens"],)).astype(np.int32)
        reqs.append(Request(
            rid=row["id"], prompt=prompt,
            max_new_tokens=row["max_new_tokens"],
            arrival=row["arrival_s"],
            deadline=(row["arrival_s"] + deadline_s if deadline_s > 0
                      else None),
            tenant=row["tenant"]))
    return reqs


def apply_trace_geometry(args, rows: list[dict]) -> None:
    """Size the bench to the trace: request count and prompt/output
    ranges come FROM the recorded geometry (the pool/max_len sizing
    flags keep their meaning; a trace longer than --max-seq still
    errors through the normal check)."""
    args.requests = len(rows)
    args.prompt_min = min(r["prompt_tokens"] for r in rows)
    args.prompt_max = max(r["prompt_tokens"] for r in rows)
    args.out_min = min(r["max_new_tokens"] for r in rows)
    args.out_max = max(r["max_new_tokens"] for r in rows)


def parse_turns_dist(spec: str):
    """`--turns-dist` grammar (ISSUE 18): `uniform:LO-HI` draws each
    session's turn count uniformly in [LO, HI]; `geometric:P` draws
    1 + Geometric(P) — most conversations short, a heavy tail of long
    ones. Returns the draw(rng) callable."""
    kind, sep, body = spec.partition(":")
    if sep and kind == "uniform":
        lo_s, dash, hi_s = body.partition("-")
        try:
            lo, hi = int(lo_s), int(hi_s)
        except ValueError:
            lo = hi = 0
        if dash and 1 <= lo <= hi:
            return lambda rng: int(rng.integers(lo, hi + 1))
        raise ValueError(
            f"turns-dist {spec!r}: uniform wants LO-HI with "
            "1 <= LO <= HI")
    if sep and kind == "geometric":
        try:
            p = float(body)
        except ValueError:
            p = 0.0
        if 0.0 < p <= 1.0:
            return lambda rng: int(rng.geometric(p))
        raise ValueError(
            f"turns-dist {spec!r}: geometric wants 0 < P <= 1")
    raise ValueError(
        f"turns-dist {spec!r}: want 'uniform:LO-HI' or 'geometric:P'")


def add_session_turns(reqs, *, turns_dist: str, turn_gap_s: float,
                      vocab: int, out_min: int, out_max: int,
                      max_len: int, seed: int):
    """Multi-turn session follow-ups (ISSUE 18): each session's FIRST
    request anchors a conversation; turn k+1 re-arrives carrying turn
    k's full context — its prompt is the previous turn's prompt plus a
    drawn continuation (the assistant reply + next user message), its
    arrival the previous turn's arrival plus an exponential think-time
    gap. That re-arriving shared context is the regime cache-aware
    routing exists for: the turn's prefix is hot on exactly one
    replica, and hash affinity only finds it by luck.

    Every draw comes from a SEPARATE (seed, 5) spawn — the --len-dist
    precedent — so the base workload is bitwise-unchanged (the pinned
    default CRCs stay valid) and turns-off runs never touch the
    stream. A chain stops when the grown prompt can no longer fit its
    next output inside `max_len` (validate_request's law). Follow-up
    rids continue from len(reqs); the merged list is re-sorted by
    (arrival, rid) — the arrival order every consumer assumes."""
    from .scheduler import Request

    draw_turns = parse_turns_dist(turns_dist)
    srng = np.random.default_rng([seed, 5])
    anchors: dict = {}
    for r in reqs:
        if r.session is not None and r.session not in anchors:
            anchors[r.session] = r
    out = list(reqs)
    rid = len(reqs)
    for sess in sorted(anchors):
        prev = anchors[sess]
        for _turn in range(draw_turns(srng) - 1):
            ext = int(srng.integers(out_min, out_max + 1))
            olen = int(srng.integers(out_min, out_max + 1))
            gap = (float(srng.exponential(turn_gap_s))
                   if turn_gap_s > 0 else 0.0)
            if prev.prompt.size + ext + olen > max_len:
                break
            prompt = np.concatenate(
                [prev.prompt,
                 srng.integers(0, vocab, (ext,)).astype(np.int32)])
            arrival = prev.arrival + gap
            rel_deadline = (prev.deadline - prev.arrival
                            if prev.deadline is not None else None)
            nr = Request(rid=rid, prompt=prompt, max_new_tokens=olen,
                         arrival=arrival,
                         deadline=(arrival + rel_deadline
                                   if rel_deadline is not None else None),
                         session=prev.session, tenant=prev.tenant)
            out.append(nr)
            rid += 1
            prev = nr
    out.sort(key=lambda r: (r.arrival, r.rid))
    return out


def diurnal_warp(reqs, *, amp: float, period_s: float):
    """Deterministic diurnal time-warp (ISSUE 18): remap each Poisson
    arrival t -> s so the instantaneous rate follows
    rate*(1 + amp*sin(2*pi*s/period)) — a day cycle with peak
    rate*(1+amp) and trough rate*(1-amp) — WITHOUT drawing anything
    (the base rate cancels out of the fixed point): s solves the
    cumulative-intensity equation Lambda(s) = t with
    Lambda(s) = s + amp*P/(2pi)*(1 - cos(2pi*s/P)), by
    fixed-iteration bisection (the map is monotone for amp <= 1, so
    arrival order is preserved and two runs bisect identically).
    amp=0 is the exact identity — the default workload CRCs stay
    pinned. Deadlines ride along at their original arrival-relative
    offset; the warp mutates in place and returns `reqs`."""
    if amp <= 0:
        return reqs
    if amp > 1.0:
        raise ValueError(f"diurnal amp must be <= 1 (got {amp}): past "
                         "it the intensity goes negative at the trough")
    if period_s <= 0:
        raise ValueError(f"diurnal period must be > 0 (got {period_s})")
    two_pi = 2.0 * np.pi
    span = amp * period_s / np.pi  # max warp displacement: Lambda bound
    for r in reqs:
        t = r.arrival
        lo, hi = max(0.0, t - span), t
        for _ in range(52):  # fixed count: bitwise-identical runs
            mid = 0.5 * (lo + hi)
            lam = mid + amp * period_s / two_pi * (
                1.0 - np.cos(two_pi * mid / period_s))
            if lam < t:
                lo = mid
            else:
                hi = mid
        s = 0.5 * (lo + hi)
        if r.deadline is not None:
            r.deadline = s + (r.deadline - r.arrival)
        r.arrival = s
    return reqs


def build_sched_policy(args, slo_spec):
    """The --scheduler/--tenant-priority/--tenant-quota surface, shared
    by serve-bench and fleet-bench (one grammar, one error story).
    Returns (rc, policy): rc nonzero means the error was printed and
    the caller should exit with it; policy is None under fcfs."""
    if args.scheduler != "slo":
        if args.tenant_priority or args.tenant_quota:
            print("error: --tenant-priority/--tenant-quota need "
                  "--scheduler slo", file=sys.stderr)
            return 2, None
        return 0, None
    from .scheduler import (
        SLOPolicy,
        parse_tenant_priorities,
        parse_tenant_quotas,
    )

    try:
        prios = (parse_tenant_priorities(args.tenant_priority)
                 if args.tenant_priority else {})
        slot_q, page_q = (parse_tenant_quotas(args.tenant_quota)
                          if args.tenant_quota else ({}, {}))
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2, None
    return 0, SLOPolicy(priorities=prios, slot_quota=slot_q,
                        page_quota=page_q, slo_spec=slo_spec)


def serve_bench_main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="mctpu serve-bench",
        description="Serving bench: paged-KV continuous batching vs "
                    "static batching under Poisson arrivals "
                    "(throughput, TTFT, p50/p99 per-token latency).",
    )
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="0 = MHA; fewer = GQA/MQA (smaller pages)")
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch rows (in-flight sequences)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page")
    ap.add_argument("--pages", type=int, default=0,
                    help="global page-pool size incl. the scratch page "
                         "(0 = size for slots full-length sequences — "
                         "ample; shrink it to exercise preemption)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--cache-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8", "auto"],
                    help="auto routes from the banked int8 table "
                         "(VERDICT 7): int8 for GQA/MQA, bfloat16 "
                         "for MHA (models/generate.pick_cache_dtype)")
    ap.add_argument("--decode-weights-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8", "auto"],
                    help="decode GEMV weights storage (ISSUE 12): int8 "
                         "= per-channel absmax QuantW via the fused "
                         "GEMV (ops/pallas_gemv), quantized ONCE at "
                         "engine construction; auto routes int8 for "
                         "GQA/MQA, float32 for MHA "
                         "(generate.pick_weights_dtype)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=96)
    ap.add_argument("--out-min", type=int, default=8)
    ap.add_argument("--out-max", type=int, default=96)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate, requests/s (0 = all at "
                         "t=0: the pure-throughput comparison)")
    ap.add_argument("--mode", default="both",
                    choices=["both", "static", "continuous"])
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline (arrival + this many ms; "
                         "0 = none): expired queued requests are "
                         "dropped, in-flight ones aborted with their "
                         "pages returned")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound on ARRIVED-but-waiting requests; "
                         "arrivals past it are rejected with a terminal "
                         "status (backpressure; 0 = unbounded)")
    ap.add_argument("--watchdog-ms", type=float, default=0.0,
                    help="tick watchdog: count + record engine "
                         "iterations slower than this (0 = off)")
    ap.add_argument("--fault-plan", default=None,
                    type=_fault_plan_arg("serve-bench"),
                    help="deterministic fault injection, e.g. "
                         "'squeeze@serve.tick:5?pages=4&ticks=8;"
                         "slow@serve.tick:9?s=0.2' (faults.parse_plan; "
                         "sites checked against serve-bench's hook "
                         "points at parse time)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="tag requests with a seeded tenant mix over "
                         "t0..t{N-1} (0 = untagged single-tenant; the "
                         "SLO layer buckets by tenant)")
    ap.add_argument("--sessions", type=int, default=0,
                    help="session keys: request i belongs to session "
                         "i %% N (0 = sessionless). On this single-"
                         "engine bench sessions only matter as the "
                         "--turns-dist conversation anchors")
    ap.add_argument("--turns-dist", default=None,
                    help="multi-turn session conversations (ISSUE 18): "
                         "'uniform:LO-HI' or 'geometric:P' turns per "
                         "session; turn k+1 re-arrives carrying turn "
                         "k's full prompt as its prefix, from a "
                         "separate seeded spawn (default workload "
                         "bitwise-unchanged; needs --sessions)")
    ap.add_argument("--turn-gap-ms", type=float, default=0.0,
                    help="mean think-time between a session's turns, "
                         "exponential draw (needs --turns-dist; 0 = "
                         "back-to-back turns)")
    ap.add_argument("--slo", default=None,
                    help="SLO spec JSON (obs.slo grammar): run the "
                         "streaming alert engine live on the record "
                         "stream; fired alerts land in the JSONL as "
                         "`alert` events")
    ap.add_argument("--prefix-mix", type=float, default=0.0,
                    help="fraction of requests sharing seeded template "
                         "prompt prefixes (ISSUE 9 workload shape; "
                         "0 = all-unique prompts, bitwise-identical "
                         "lengths/arrivals either way)")
    ap.add_argument("--len-dist", default="uniform",
                    choices=["uniform", "lognormal"],
                    help="prompt/output length mix (ISSUE 16): uniform "
                         "over the ranges (default, bitwise-unchanged "
                         "stream) or a heavy-tail lognormal clipped to "
                         "them, drawn from a separate seeded spawn")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable prefix-sharing KV cache on the "
                         "continuous scheduler: hash-keyed prefix "
                         "pages with refcounts + COW — cache-hit "
                         "requests prefill only their suffix")
    ap.add_argument("--templates", type=int, default=0,
                    help="prefix template working-set size (ISSUE 17): "
                         "overrides the default 4-template pool with N "
                         "templates drawn from a separate seeded spawn "
                         "(default workload bitwise-unchanged); size it "
                         "past the device page pool to exercise the "
                         "host tier (needs --prefix-mix > 0)")
    ap.add_argument("--spill", action="store_true",
                    help="host-tier KV spill (ISSUE 17): LRU-reclaimed "
                         "refcount-0 prefix pages spill to a bounded "
                         "host-memory tier instead of being discarded; "
                         "a later prefix hit readmits them (CRC-sealed "
                         "at the tier crossing — corrupt spills are "
                         "refused and re-prefill). Needs --prefix-cache")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host-tier capacity in pages (--spill; 0 = "
                         "match the device pool)")
    ap.add_argument("--spec", default="off",
                    choices=["off", "lookup", "draft"],
                    help="batched speculative decoding (ISSUE 14), "
                         "continuous mode only: lookup = draft-free "
                         "prompt lookup over each request's committed "
                         "context (the agentic/template-traffic form); "
                         "draft = a cheap sliding-window draft model "
                         "behind the same interface. Per tick: per-slot "
                         "k-token proposal + ONE batched verify block; "
                         "T=0 outputs stay bitwise spec-off's while "
                         "the tick count drops with acceptance")
    ap.add_argument("--spec-k", type=int, default=8,
                    help="speculative round width: candidate tokens "
                         "verified per slot per tick (>= 2)")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="prompt-lookup match length (--spec lookup)")
    ap.add_argument("--draft-dim", type=int, default=0,
                    help="draft model width (--spec draft; 0 = dim/2)")
    ap.add_argument("--draft-depth", type=int, default=0,
                    help="draft model depth (--spec draft; 0 = 1)")
    ap.add_argument("--draft-cache", default="window",
                    choices=["window", "paged"],
                    help="draft KV form (--spec draft, ISSUE 17): "
                         "window = cacheless sliding-window draft "
                         "(recomputes ~W tokens per proposal); paged = "
                         "the draft holds its own paged KV cache, "
                         "per-slot block tables growing/rolling back in "
                         "lockstep with commit_spec (same T=0 outputs, "
                         "~W x fewer draft FLOPs per round)")
    ap.add_argument("--scheduler", default="fcfs",
                    choices=["fcfs", "slo"],
                    help="continuous-batching policy: fcfs (default) "
                         "or the SLO-aware scheduler (priority "
                         "classes, per-tenant quotas, burn-driven "
                         "preemption; implies --mode continuous)")
    ap.add_argument("--tenant-priority", default=None,
                    help="per-tenant priority classes, e.g. "
                         "'t0=2,t1=0' (higher = more protected; "
                         "needs --scheduler slo)")
    ap.add_argument("--tenant-quota", default=None,
                    help="per-tenant admission quotas, e.g. "
                         "'t0=pages:8/slots:2,t1=slots:1' "
                         "(needs --scheduler slo)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None,
                    help="trace-driven replay (ROADMAP item 4): rebuild "
                         "the workload from a finished run's metrics "
                         "JSONL `request` records — ids, prompt/output "
                         "budgets, arrivals, and tenant labels exactly "
                         "as recorded (prompt content re-synthesized "
                         "per id from --seed); overrides --requests, "
                         "--rate and the length-range flags")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append per-request obs records here")
    ap.add_argument("--device", default="auto",
                    choices=["auto", "tpu", "cpu"])
    args = ap.parse_args(argv)

    trace_rows = None
    if args.trace:
        if args.turns_dist or args.prefix_mix > 0 or args.templates:
            # Loud-config-error convention: these flags shape generated
            # prompts; a trace IS the workload, so they would silently
            # describe a run that never happens.
            print("error: --trace replaces the generated workload; "
                  "drop --turns-dist/--prefix-mix/--templates",
                  file=sys.stderr)
            return 2
        try:
            trace_rows = load_trace(args.trace)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        apply_trace_geometry(args, trace_rows)

    import jax

    from ..utils.backend import DeviceError, claim_device

    try:
        stamp = claim_device(args.device)
    except DeviceError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1

    from ..models.transformer import TransformerLM
    from ..obs.causal import CATEGORIES, BlameAccumulator
    from ..obs.metrics import MetricsRegistry
    from ..utils.logging import MetricsLogger
    from .engine import PagedEngine
    from .paged_cache import pages_for

    if args.prompt_max + args.out_max > args.max_seq:
        print(f"prompt {args.prompt_max} + out {args.out_max} exceeds "
              f"--max-seq {args.max_seq}", file=sys.stderr)
        return 1
    from ..models.generate import pick_cache_dtype

    cache_dtype = pick_cache_dtype(args.cache_dtype, heads=args.heads,
                                   kv_heads=args.kv_heads or None)
    if args.spec != "off" and args.mode == "static":
        # Same contract as --prefix-cache: speculation is iteration-
        # level; a pure-static run would silently measure spec-off.
        print("error: --spec needs continuous batching (--mode "
              "continuous or both; static is the one-token baseline)",
              file=sys.stderr)
        return 2
    if args.spec != "off" and args.spec_k < 2:
        print(f"error: --spec-k {args.spec_k} would propose nothing "
              "(want >= 2)", file=sys.stderr)
        return 2
    if args.draft_cache == "paged" and args.spec != "draft":
        # Loud-config-error convention: the knob only shapes the draft
        # proposer; swept without one it would silently measure nothing.
        print("error: --draft-cache paged needs --spec draft",
              file=sys.stderr)
        return 2
    if args.spill and not args.prefix_cache:
        print("error: --spill needs --prefix-cache (the host tier "
              "spills prefix-cache pages; there is nothing to spill)",
              file=sys.stderr)
        return 2
    if args.host_pages and not args.spill:
        print("error: --host-pages needs --spill (without the tier the "
              "capacity knob would be silently ignored)",
              file=sys.stderr)
        return 2
    if args.templates and not args.prefix_mix > 0:
        print("error: --templates needs --prefix-mix > 0 (no request "
              "draws a template prefix at mix 0)", file=sys.stderr)
        return 2
    if args.turns_dist and args.sessions <= 0:
        print("error: --turns-dist needs --sessions > 0 (turns are "
              "per-session conversations; a sessionless workload has "
              "no chains to grow)", file=sys.stderr)
        return 2
    if args.turn_gap_ms and not args.turns_dist:
        print("error: --turn-gap-ms needs --turns-dist (without turns "
              "there are no gaps to draw)", file=sys.stderr)
        return 2
    if args.turns_dist:
        try:
            parse_turns_dist(args.turns_dist)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    model = TransformerLM(
        vocab=args.vocab, dim=args.dim, heads=args.heads, depth=args.depth,
        max_seq=args.max_seq, kv_heads=args.kv_heads,
    )
    params = model.init(jax.random.key(args.seed))
    max_len = args.prompt_max + args.out_max
    pages = args.pages or args.slots * pages_for(max_len, args.page_size) + 1
    draft_model = draft_params = None
    if args.spec == "draft":
        # The cheap draft: narrower/shallower, same vocab/heads — its
        # params come from a DIFFERENT key so the draft is a genuinely
        # distinct model (a draft equal to the target would accept
        # everything and measure nothing).
        draft_model = TransformerLM(
            vocab=args.vocab, dim=args.draft_dim or max(args.dim // 2, 16),
            heads=args.heads, depth=args.draft_depth or 1,
            max_seq=args.max_seq, kv_heads=args.kv_heads,
        )
        draft_params = draft_model.init(jax.random.key(args.seed + 1))
    engine = PagedEngine(
        model, params, slots=args.slots, num_pages=pages,
        page_size=args.page_size, prefill_chunk=args.prefill_chunk,
        cache_dtype=cache_dtype, max_len=max_len,
        weights_dtype=args.decode_weights_dtype,
        spec=args.spec, spec_k=args.spec_k, spec_ngram=args.spec_ngram,
        draft_model=draft_model, draft_params=draft_params,
        draft_cache=args.draft_cache,
    )
    host_pages = (args.host_pages or pages) if args.spill else 0
    if args.scheduler == "slo":
        args.mode = "continuous"
    if args.prefix_cache and args.mode == "static":
        # Sharing is continuous-only (static is the reservation
        # baseline); running it silently sharing-off would report a
        # measurement the flags don't describe.
        print("error: --prefix-cache needs continuous batching "
              "(--mode continuous or both; static is the sharing-off "
              "baseline)", file=sys.stderr)
        return 2
    modes = (["static", "continuous"] if args.mode == "both"
             else [args.mode])
    workload_kw = dict(
        n=args.requests, vocab=args.vocab, prompt_min=args.prompt_min,
        prompt_max=args.prompt_max, out_min=args.out_min,
        out_max=args.out_max, rate=args.rate, seed=args.seed,
        deadline_s=args.deadline_ms / 1e3, tenants=args.tenants,
        prefix_mix=args.prefix_mix, len_dist=args.len_dist,
        templates=args.templates,
    )
    run_kw = dict(
        max_queue=args.max_queue or None,
        watchdog_s=args.watchdog_ms / 1e3,
    )

    def build_reqs():
        # Regenerated identically per mode (the cross-mode contract);
        # session tags + multi-turn follow-ups (ISSUE 18) layer on top
        # of the base stream without perturbing it.
        if trace_rows is not None:
            reqs = requests_from_trace(
                trace_rows, vocab=args.vocab, seed=args.seed,
                deadline_s=args.deadline_ms / 1e3)
        else:
            reqs = make_workload(**workload_kw)
        if args.sessions > 0:
            for r in reqs:
                r.session = r.rid % args.sessions
        if args.turns_dist:
            reqs = add_session_turns(
                reqs, turns_dist=args.turns_dist,
                turn_gap_s=args.turn_gap_ms / 1e3, vocab=args.vocab,
                out_min=args.out_min, out_max=args.out_max,
                max_len=max_len, seed=args.seed)
        return reqs
    alert_engine = None
    slo_spec = None
    if args.slo:
        from ..obs.alerts import AlertEngine
        from ..obs.slo import SLOSpec

        try:
            slo_spec = SLOSpec.load(args.slo)
            alert_engine = AlertEngine(slo=slo_spec)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    rc, sched_policy = build_sched_policy(args, slo_spec)
    if rc:
        return rc
    summaries = {}
    with MetricsLogger(path=args.metrics_jsonl, echo=False) as metrics:
        metrics.log("device", **stamp)
        if alert_engine is not None:
            # Live alerting folds EXACTLY the records the file gets
            # (MetricsLogger observer): replaying the finished JSONL
            # reproduces the identical alert sequence, CRC-pinned.
            alert_engine.attach(metrics)
        # Warm the compiled programs (engine-level: the same ones serve
        # every mode) on one throwaway request, so no mode pays
        # compilation inside its latencies. With sharing on, the COW
        # copy program warms too (scratch onto itself — harmless).
        engine.run(make_workload(**{**workload_kw, "n": 1, "rate": 0.0,
                                    "deadline_s": 0.0}),
                   mode=modes[0])
        if args.spec != "off":
            # Warm the speculative verify program too (one continuous
            # spec round on the throwaway request).
            engine.run(make_workload(**{**workload_kw, "n": 1, "rate": 0.0,
                                        "deadline_s": 0.0}),
                       mode="continuous", spec=True)
        if args.prefix_cache:
            engine.copy_page(0, 0)
        if args.spill:
            # Warm the readmission restore program (scratch page onto
            # itself, like the COW warm-up above — harmless: scratch is
            # the sanctioned garbage sink).
            engine.readmit_page(0, engine.spill_page(0))
        for mode in modes:
            faults = None
            if args.fault_plan:
                # Fresh injector per mode: both modes see the identical
                # fault schedule (the cross-mode comparison contract).
                from ..faults import FaultInjector

                faults = FaultInjector(args.fault_plan)
            # The runtime metrics layer (ISSUE 6): one registry per mode
            # (cross-mode aggregation would blend the two schedules) and
            # tick records streamed to the JSONL sink AS THEY HAPPEN —
            # `mctpu top run.jsonl` tails the file live; `mctpu trace`
            # reconstructs lifecycles from the same records afterwards.
            registry = MetricsRegistry()
            base_sink = None
            if metrics.jsonl_enabled or alert_engine is not None:
                # Tick records route through metrics.log either way:
                # the JSONL sink and the alert observer both hang off
                # it (with no file open, log() is observer-only).
                def base_sink(rec, _snap_every=64):
                    metrics.log("tick", **rec)
                    if (rec["tick"] + 1) % _snap_every == 0:
                        registry.emit(metrics, mode=rec["mode"])
            # Causal blame (ISSUE 11) folds the live tick stream the
            # way the alert engine does — always on, so every serve
            # summary carries blame_crc + per-category totals whether
            # or not the ticks reach a file.
            blame = BlameAccumulator()

            def tick_sink(rec, _base=base_sink):
                blame.ingest_tick(rec)
                if _base is not None:
                    _base(rec)
            result = engine.run(build_reqs(), mode=mode,
                                faults=faults, registry=registry,
                                tick_sink=tick_sink,
                                prefix=(args.prefix_cache
                                        and mode == "continuous"),
                                policy=(sched_policy
                                        if mode == "continuous" else None),
                                spec=(args.spec != "off"
                                      and mode == "continuous"),
                                host_pages=(host_pages
                                            if mode == "continuous" else 0),
                                **run_kw)
            s = result.summary()
            # Blame stamp (ISSUE 11): the crc + per-category totals
            # `mctpu compare` flattens as serve.<mode>.blame_*, plus
            # the full `blame` summary record for `mctpu report`.
            bf = blame.summary_fields(mode)
            s["blame_crc"] = bf["crc"]
            s["blame_quota_ticks"] = bf["quota_ticks"]
            for cat in CATEGORIES:
                s[f"blame_{cat}"] = bf["categories"][cat]
            metrics.log("blame", **bf)
            summaries[mode] = s
            registry.set("serve.tokens_per_s", s["tokens_per_s"])
            registry.emit(metrics, mode=mode, final=True)
            for rec in result.request_records():
                metrics.log("request", **rec)
            for ev in result.events:
                metrics.log("fault", **{"mode": mode, **ev})
            metrics.log("serve", **{
                "bench": "serve", "backend": jax.default_backend(),
                "cache_dtype": cache_dtype, "rate": args.rate,
                "weights_dtype": engine.weights_dtype,
                "spec": args.spec, "spec_k": args.spec_k,
                "slots": args.slots, "page_size": args.page_size,
                "pages": pages,
                # Whether the continuous run shared prefixes (ISSUE 15):
                # the replay reconstruction needs the flag — a sharing-on
                # run with zero hits digests (0,0,...) where a
                # sharing-off run digests None.
                "prefix_cache": bool(args.prefix_cache),
                # Host-tier + draft-cache geometry (ISSUE 17): the
                # replay mirror rebuilds the tier digest extension from
                # host_pages > 0 and the draft-pool extension from
                # draft_cache == "paged" (max_len sizes the draft pool).
                "host_pages": host_pages,
                "draft_cache": args.draft_cache,
                "max_len": max_len, **s,
            })
            print(json.dumps({"bench": "serve", "backend":
                              jax.default_backend(),
                              "cache_dtype": cache_dtype,
                                            "weights_dtype": engine.weights_dtype,
                              "spec": args.spec, "spec_k": args.spec_k,
                              **s}))
    if alert_engine is not None:
        print(json.dumps({"metric": "serve_alerts_fired",
                          "value": len(alert_engine.alerts),
                          "alerts_crc": alert_engine.crc}))
    if len(summaries) == 2:
        st, ct = summaries["static"], summaries["continuous"]
        print(json.dumps({
            "metric": "serve_tokens_per_s",
            "value": ct["tokens_per_s"],
            "unit": "tokens/s",
            "static_tokens_per_s": st["tokens_per_s"],
            "speedup": round(ct["tokens_per_s"] / max(st["tokens_per_s"],
                                                      1e-9), 3),
            "decode_ticks": {"static": st["decode_ticks"],
                             "continuous": ct["decode_ticks"]},
            "ttft_p99_ms": {"static": st["ttft_p99_ms"],
                            "continuous": ct["ttft_p99_ms"]},
        }))
    return 0


def fleet_bench_main(argv: list[str] | None = None) -> int:
    """`mctpu fleet-bench` — the multi-replica storm harness (ISSUE 7).

    Everything host-side runs on a FakeClock advanced `--tick-ms` per
    fleet tick, so the schedule — dispatches, failovers, re-dispatches
    — is a pure function of (workload seed, fault plan, fleet shape):
    two identical invocations are bitwise-equal in dispatch trace and
    per-status counts, which is exactly what CI's fleet determinism
    gate compares (`mctpu compare ... --gate ci/fleet_gate.json`).
    Latency/throughput figures are in fleet-clock units unless marked
    wall_*.
    """
    ap = argparse.ArgumentParser(
        prog="mctpu fleet-bench",
        description="Failure-aware fleet bench: N single-engine "
                    "replicas behind the router under a seeded Poisson "
                    "storm, with optional injected replica crashes / "
                    "joins / leaves (exactly-once re-dispatch).",
    )
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--pools", default=None,
                    help="disaggregated prefill/decode serving "
                         "(ISSUE 13): 'prefill:N,decode:M' splits the "
                         "fleet by phase — arrivals dispatch to the "
                         "prefill pool, completed prefills hand their "
                         "KV page sets to decode replicas through the "
                         "crash-safe page-granular transfer protocol "
                         "(per-page CRCs, per-handoff fences); "
                         "overrides --replicas. An emptied pool "
                         "degrades affected requests to unified "
                         "serving instead of stalling")
    ap.add_argument("--handoff-ticks", type=int, default=1,
                    help="fleet ticks one KV handoff's copy is in "
                         "flight (the mid-handoff crash window; "
                         "needs --pools)")
    ap.add_argument("--policy", default="least_loaded",
                    choices=["least_loaded", "session", "cache_aware"],
                    help="dispatch policy: least_loaded, session "
                         "(rendezvous-hash affinity), or cache_aware "
                         "(ISSUE 18: score candidates by expected "
                         "prefix-token overlap against each replica's "
                         "live routing digest — device tree + host "
                         "tier; least-loaded tie-break, hash-affinity "
                         "fallback at zero overlap. Needs "
                         "--prefix-cache)")
    ap.add_argument("--redispatch", default="resume",
                    choices=["resume", "discard"],
                    help="failover semantics for in-flight requests: "
                         "resume re-prefills prompt + committed tokens "
                         "on the new replica; discard restarts from "
                         "the prompt")
    ap.add_argument("--heartbeat-miss", type=int, default=3,
                    help="consecutive missed heartbeat ticks before a "
                         "replica is declared dead")
    ap.add_argument("--transport", action="store_true",
                    help="route the control plane over the simulated "
                         "lossy message bus (ISSUE 20): dispatch, "
                         "commits, terminals, and heartbeats become "
                         "sequenced messages with at-least-once "
                         "retransmission + receiver dedup; fences gain "
                         "lease expiries and failure detection becomes "
                         "fallible (late != dead). Zero-fault runs stay "
                         "bitwise-equal to the direct-call fleet; "
                         "unlocks the fleet.transport fault site")
    ap.add_argument("--lease-ticks", type=int, default=0,
                    help="commit-lease lifetime in fleet ticks "
                         "(--transport; 0 = heartbeat_miss + 2; must "
                         "exceed --heartbeat-miss so a live replica's "
                         "heartbeats renew faster than its lease decays)")
    ap.add_argument("--rto-base", type=float, default=2.0,
                    help="retransmission-timeout base in fleet ticks "
                         "(--transport; utils/retry.backoff_delay-paced "
                         "exponential, deterministic zero-jitter)")
    ap.add_argument("--max-flaps", type=int, default=3,
                    help="crashes before a flapping replica's circuit "
                         "opens (it never rejoins)")
    ap.add_argument("--backoff-base", type=float, default=0.05,
                    help="restart backoff base, fleet-clock seconds "
                         "(utils/retry.backoff_delay; 0 = immediate)")
    ap.add_argument("--tick-ms", type=float, default=1.0,
                    help="fleet-clock advance per tick")
    ap.add_argument("--check-every", type=int, default=16,
                    help="page-pool invariant check cadence per replica "
                         "(1 = every step; always checked at exit)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=0,
                    help="pages per replica incl. scratch (0 = size for "
                         "slots full-length sequences)")
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--max-queue", type=int, default=0,
                    help="per-replica bound on waiting arrivals "
                         "(0 = unbounded)")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--prompt-min", type=int, default=8)
    ap.add_argument("--prompt-max", type=int, default=96)
    ap.add_argument("--out-min", type=int, default=8)
    ap.add_argument("--out-max", type=int, default=96)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate in fleet-clock req/s "
                         "(0 = everything at t=0)")
    ap.add_argument("--sessions", type=int, default=0,
                    help="session keys for the affinity policy: request "
                         "i belongs to session i %% N (0 = sessionless)")
    ap.add_argument("--turns-dist", default=None,
                    help="multi-turn session conversations (ISSUE 18): "
                         "'uniform:LO-HI' or 'geometric:P' turns per "
                         "session; turn k+1 re-arrives carrying turn "
                         "k's full prompt as its prefix, from a "
                         "separate seeded spawn (default workload "
                         "bitwise-unchanged; needs --sessions)")
    ap.add_argument("--turn-gap-ms", type=float, default=0.0,
                    help="mean think-time between a session's turns in "
                         "fleet-clock ms, exponential draw (needs "
                         "--turns-dist; 0 = back-to-back turns)")
    ap.add_argument("--diurnal-amp", type=float, default=0.0,
                    help="diurnal arrival modulation depth (ISSUE 18): "
                         "time-warp the Poisson arrivals so the rate "
                         "follows rate*(1 + amp*sin) over "
                         "--diurnal-period — peak rate*(1+amp), trough "
                         "rate*(1-amp); 0 = identity (default stream "
                         "bitwise-unchanged), max 1. Needs --rate > 0")
    ap.add_argument("--diurnal-period", type=float, default=10.0,
                    help="diurnal cycle length, fleet-clock seconds "
                         "(--diurnal-amp)")
    ap.add_argument("--autoscale", default=None,
                    help="online goodput autoscaler (ISSUE 18): fold "
                         "live queue pressure, SLO burn rates (--slo), "
                         "and the autosize frontier target "
                         "(--autoscale-frontier) into replica "
                         "join/leave decisions each tick. Grammar: "
                         "comma-separated key=value over min/max/high/"
                         "low/up/down/cooldown/burn, or bare 'on' for "
                         "defaults (serve/autoscale.parse_autoscale)")
    ap.add_argument("--autoscale-frontier", default=None,
                    help="goodput JSONL from `mctpu autosize "
                         "--metrics-jsonl`: its frontier record's "
                         "best_per_chip_rps converts the observed "
                         "dispatch rate into a target replica count "
                         "(needs --autoscale)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="tag requests with a seeded tenant mix over "
                         "t0..t{N-1} (0 = untagged single-tenant; the "
                         "SLO layer buckets by tenant)")
    ap.add_argument("--slo", default=None,
                    help="SLO spec JSON (obs.slo grammar): run the "
                         "streaming alert engine live; with --log "
                         "summary the engine taps the per-tick sinks "
                         "directly (the records stay out of the JSONL, "
                         "the alerts land in it). Summary gains "
                         "alerts_fired/alerts_crc either way")
    ap.add_argument("--prefix-mix", type=float, default=0.0,
                    help="fraction of requests sharing seeded template "
                         "prompt prefixes (ISSUE 9; 0 = all-unique)")
    ap.add_argument("--len-dist", default="uniform",
                    choices=["uniform", "lognormal"],
                    help="prompt/output length mix (ISSUE 16): uniform "
                         "(default, bitwise-unchanged stream) or "
                         "heavy-tail lognormal from a separate spawn")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="per-replica prefix-sharing KV cache: "
                         "cache-hit requests prefill only their suffix "
                         "(restarted incarnations come back cold)")
    ap.add_argument("--templates", type=int, default=0,
                    help="prefix template working-set size (ISSUE 17): "
                         "N templates from a separate seeded spawn "
                         "(default workload bitwise-unchanged; needs "
                         "--prefix-mix > 0)")
    ap.add_argument("--spill", action="store_true",
                    help="per-replica host-tier KV spill (ISSUE 17): "
                         "LRU-reclaimed prefix pages spill to a bounded "
                         "host tier and readmit on the next hit "
                         "(CRC-sealed; sim compute is accounting-only). "
                         "A restarted incarnation drops its tier with "
                         "its pool. Needs --prefix-cache")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host-tier capacity per replica in pages "
                         "(--spill; 0 = match the device pool)")
    ap.add_argument("--spec", default="off",
                    choices=["off", "lookup"],
                    help="per-replica batched speculative decoding "
                         "(ISSUE 14): lookup = draft-free prompt "
                         "lookup; every replica (and every restarted "
                         "incarnation) speculates identically, so the "
                         "dispatch trace stays seed-deterministic "
                         "(model-draft is a serve-bench/engine surface)")
    ap.add_argument("--spec-k", type=int, default=8,
                    help="speculative round width per slot per tick")
    ap.add_argument("--spec-ngram", type=int, default=2,
                    help="prompt-lookup match length (--spec lookup)")
    ap.add_argument("--scheduler", default="fcfs",
                    choices=["fcfs", "slo"],
                    help="per-replica batching policy: fcfs or the "
                         "SLO-aware scheduler (priorities, quotas, "
                         "burn-driven preemption)")
    ap.add_argument("--tenant-priority", default=None,
                    help="per-tenant priority classes, e.g. 't0=2,t1=0'"
                         " (higher = more protected; --scheduler slo)")
    ap.add_argument("--tenant-quota", default=None,
                    help="per-tenant admission quotas, e.g. "
                         "'t0=pages:8/slots:2' (--scheduler slo)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request fleet-clock deadline (0 = none)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None,
                    help="trace-driven replay (ROADMAP item 4): feed a "
                         "recorded request trail (any finished run's "
                         "metrics JSONL) back through the fleet — ids, "
                         "prompt/output budgets, arrivals, and tenant "
                         "labels exactly as recorded (prompt content "
                         "re-synthesized per id from --seed); overrides "
                         "--requests, --rate and the length-range flags")
    ap.add_argument("--fault-plan", default=None,
                    type=_fault_plan_arg("fleet-bench"),
                    help="deterministic replica faults, e.g. "
                         "'replica_crash@fleet.tick:40?replica=1&"
                         "zombie_ticks=3;replica_join@fleet.tick:90' "
                         "(sites checked against fleet-bench's hook "
                         "points at parse time)")
    ap.add_argument("--compute", default="sim", choices=["sim", "engine"],
                    help="sim: device-free pure-token replicas (the "
                         "10^5-storm scale mode); engine: one real "
                         "PagedEngine per replica, shared weights")
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=0)
    ap.add_argument("--cache-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8", "auto"],
                    help="auto routes int8 for GQA/MQA, bfloat16 for "
                         "MHA (models/generate.pick_cache_dtype)")
    ap.add_argument("--decode-weights-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8", "auto"],
                    help="decode GEMV weights per engine replica "
                         "(ISSUE 12; engine compute only; auto = int8 "
                         "for GQA/MQA, float32 for MHA)")
    ap.add_argument("--device", default="auto",
                    choices=["auto", "tpu", "cpu"])
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append obs records here (fleet/replica/"
                         "request/fault events + registry snapshots)")
    ap.add_argument("--log", default="full", choices=["full", "summary"],
                    help="full: per-tick fleet + per-replica tick + "
                         "per-request records (what `mctpu trace`/`top` "
                         "consume); summary: lifecycle + totals only "
                         "(the 10^5-storm mode — per-tick JSONL would "
                         "dominate the run)")
    args = ap.parse_args(argv)

    from ..faults import FakeClock, FaultInjector
    from ..obs.causal import CATEGORIES, BlameAccumulator
    from ..obs.metrics import MetricsRegistry
    from ..utils.logging import MetricsLogger
    from .fleet import (
        EngineCompute,
        Fleet,
        SimCompute,
        make_fleet_workload,
        parse_pools,
    )
    from .paged_cache import pages_for

    pools = None
    if args.pools:
        try:
            pools = parse_pools(args.pools)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    elif args.handoff_ticks != 1:
        # The loud-config-error convention: a unified fleet has no
        # handoffs, so a swept --handoff-ticks would be silently
        # ignored and every run would measure the same thing.
        print("error: --handoff-ticks needs --pools (a unified fleet "
              "performs no KV handoffs)", file=sys.stderr)
        return 2

    if args.lease_ticks and not args.transport:
        print("error: --lease-ticks needs --transport (leases pace the "
              "bus's commit fences; the direct-call fleet has no wire "
              "to lease against)", file=sys.stderr)
        return 2
    if args.rto_base != 2.0 and not args.transport:
        print("error: --rto-base needs --transport (there are no "
              "retransmissions without the bus)", file=sys.stderr)
        return 2
    if args.spill and not args.prefix_cache:
        print("error: --spill needs --prefix-cache (the host tier "
              "spills prefix-cache pages; there is nothing to spill)",
              file=sys.stderr)
        return 2
    if args.host_pages and not args.spill:
        print("error: --host-pages needs --spill (without the tier the "
              "capacity knob would be silently ignored)",
              file=sys.stderr)
        return 2
    if args.templates and not args.prefix_mix > 0:
        print("error: --templates needs --prefix-mix > 0 (no request "
              "draws a template prefix at mix 0)", file=sys.stderr)
        return 2
    if args.policy == "cache_aware" and not args.prefix_cache:
        print("error: --policy cache_aware needs --prefix-cache (the "
              "score is expected prefix-cache overlap; without the "
              "cache every score is zero and the policy silently "
              "degrades to its fallback)", file=sys.stderr)
        return 2
    if args.turns_dist and args.sessions <= 0:
        print("error: --turns-dist needs --sessions > 0 (turns are "
              "per-session conversations; a sessionless workload has "
              "no chains to grow)", file=sys.stderr)
        return 2
    if args.turn_gap_ms and not args.turns_dist:
        print("error: --turn-gap-ms needs --turns-dist (without turns "
              "there are no gaps to draw)", file=sys.stderr)
        return 2
    if args.diurnal_amp > 0 and args.rate <= 0:
        print("error: --diurnal-amp needs --rate > 0 (rate 0 puts "
              "every arrival at t=0; there is no arrival process to "
              "modulate)", file=sys.stderr)
        return 2
    if args.diurnal_amp > 1.0:
        print(f"error: diurnal amp must be <= 1 (got {args.diurnal_amp})"
              ": past it the intensity goes negative at the trough",
              file=sys.stderr)
        return 2
    if args.autoscale_frontier and not args.autoscale:
        print("error: --autoscale-frontier needs --autoscale (the "
              "frontier is the autoscaler's lookup table; without the "
              "policy it would be silently ignored)", file=sys.stderr)
        return 2
    if args.turns_dist:
        try:
            parse_turns_dist(args.turns_dist)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    trace_rows = None
    if args.trace:
        if (args.turns_dist or args.prefix_mix > 0 or args.templates
                or args.diurnal_amp > 0):
            # Loud-config-error convention: these flags shape generated
            # prompts/arrivals; a trace IS the workload.
            print("error: --trace replaces the generated workload; "
                  "drop --turns-dist/--prefix-mix/--templates/"
                  "--diurnal-amp", file=sys.stderr)
            return 2
        try:
            trace_rows = load_trace(args.trace)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        apply_trace_geometry(args, trace_rows)
    max_len = args.prompt_max + args.out_max
    pages = args.pages or args.slots * pages_for(max_len, args.page_size) + 1
    host_pages = (args.host_pages or pages) if args.spill else 0
    stamp = None  # SimCompute fleets are jax-free: no device to name
    if args.compute == "engine":
        import jax

        from ..utils.backend import DeviceError, claim_device

        try:
            stamp = claim_device(args.device)
        except DeviceError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        from ..models.transformer import TransformerLM
        from .engine import PagedEngine

        model = TransformerLM(
            vocab=args.vocab, dim=args.dim, heads=args.heads,
            depth=args.depth, max_seq=max_len, kv_heads=args.kv_heads,
        )
        params = model.init(jax.random.key(args.seed))

        def compute_factory(name):
            # One engine (own page pools) per replica INCARNATION: a
            # restarted replica comes back with an empty cache. The
            # weights are shared — same params on every replica, which
            # is what makes cross-replica re-dispatch output-exact.
            return EngineCompute(PagedEngine(
                model, params, slots=args.slots, num_pages=pages,
                page_size=args.page_size, prefill_chunk=args.prefill_chunk,
                cache_dtype=args.cache_dtype, max_len=max_len,
                        weights_dtype=args.decode_weights_dtype,
                spec=args.spec, spec_k=args.spec_k,
                spec_ngram=args.spec_ngram,
            ))
    else:
        def compute_factory(name):
            return SimCompute(vocab=args.vocab, chunk=args.prefill_chunk,
                              salt=args.seed)

    try:
        if trace_rows is not None:
            reqs = requests_from_trace(
                trace_rows, vocab=args.vocab, seed=args.seed,
                deadline_s=args.deadline_ms / 1e3)
            if args.sessions > 0:
                for r in reqs:
                    r.session = r.rid % args.sessions
        else:
            reqs = make_fleet_workload(
                n=args.requests, vocab=args.vocab,
                prompt_min=args.prompt_min,
                prompt_max=args.prompt_max, out_min=args.out_min,
                out_max=args.out_max, rate=args.rate, seed=args.seed,
                sessions=args.sessions, deadline_s=args.deadline_ms / 1e3,
                tenants=args.tenants, prefix_mix=args.prefix_mix,
                len_dist=args.len_dist, templates=args.templates,
                turns_dist=args.turns_dist,
                turn_gap_s=args.turn_gap_ms / 1e3,
                diurnal_amp=args.diurnal_amp,
                diurnal_period_s=args.diurnal_period,
            )
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    alert_engine = None
    slo_spec = None
    if args.slo:
        from ..obs.alerts import AlertEngine
        from ..obs.slo import SLOSpec

        try:
            slo_spec = SLOSpec.load(args.slo)
            alert_engine = AlertEngine(slo=slo_spec)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
    rc, sched_policy = build_sched_policy(args, slo_spec)
    if rc:
        return rc
    autoscaler = None
    if args.autoscale:
        from .autoscale import Autoscaler, load_frontier, parse_autoscale

        try:
            pol = parse_autoscale(args.autoscale)
            per_chip = (load_frontier(args.autoscale_frontier)
                        if args.autoscale_frontier else 0.0)
        except (OSError, ValueError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        # slo_spec switches the burn-rate feed on: the autoscaler runs
        # the SAME windowed Accountant fold the alert engine does, over
        # the fence-accepted terminal stream.
        autoscaler = Autoscaler(pol, slo_spec=slo_spec,
                                per_chip_rps=per_chip)
    clock = FakeClock()
    registry = MetricsRegistry(clock=clock)
    faults = FaultInjector(args.fault_plan) if args.fault_plan else None
    with MetricsLogger(path=args.metrics_jsonl, echo=False) as metrics:
        if stamp is not None:
            metrics.log("device", **stamp)
        if alert_engine is not None:
            # Everything that goes through metrics.log (registry
            # snapshots, replica/fault/request/serve records — and, at
            # --log full, the tick/fleet stream) is folded live; the
            # fired alerts are logged straight back as `alert` events.
            alert_engine.attach(metrics)
        base_fleet = base_replica = None
        if metrics.jsonl_enabled and args.log == "full":
            def base_fleet(rec):
                metrics.log("fleet", **rec)

            def base_replica(rec):
                metrics.log("tick", **rec)
        elif alert_engine is not None:
            # Summary mode keeps per-tick records OUT of the JSONL (at
            # 10^5 requests they would dominate the run) but the live
            # rule engine still sees them: tap the sinks directly.
            # Replay-from-file cannot reproduce these alerts — that
            # contract needs --log full; the determinism CI instead
            # pins alerts_crc across two identical-seed runs.
            def base_fleet(rec):
                for a in alert_engine.ingest(rec, event="fleet"):
                    metrics.log("alert", **a)

            def base_replica(rec):
                for a in alert_engine.ingest(rec, event="tick"):
                    metrics.log("alert", **a)
        # Causal blame (ISSUE 11): ALWAYS folded live off the sinks,
        # like the alert engine under --log summary — the determinism
        # gate pins blame_crc + per-category totals on every fleet-
        # bench run, including the 10^5 storm whose per-tick records
        # never reach the JSONL.
        blame = BlameAccumulator()

        def fleet_sink(rec, _base=base_fleet):
            blame.ingest_fleet(rec)
            if _base is not None:
                _base(rec)

        def replica_tick_sink(rec, _base=base_replica):
            blame.ingest_tick(rec)
            if _base is not None:
                _base(rec)
        try:
            fleet = Fleet(
                compute_factory, replicas=args.replicas, slots=args.slots,
                num_pages=pages, page_size=args.page_size, max_len=max_len,
                max_queue=args.max_queue or None, policy=args.policy,
                heartbeat_miss=args.heartbeat_miss,
                backoff_base=args.backoff_base, max_flaps=args.max_flaps,
                redispatch=args.redispatch, tick_s=args.tick_ms / 1e3,
                check_every=args.check_every, faults=faults, clock=clock,
                registry=registry, fleet_sink=fleet_sink,
                replica_tick_sink=replica_tick_sink,
                prefix=args.prefix_cache, sched_policy=sched_policy,
                host_pages=host_pages,
                spec=args.spec, spec_k=args.spec_k,
                spec_ngram=args.spec_ngram,
                pools=pools, handoff_ticks=args.handoff_ticks,
                autoscale=autoscaler,
                transport=args.transport, lease_ticks=args.lease_ticks,
                rto_base=args.rto_base,
                # The per-transfer lifecycle log is only ever emitted at
                # --log full; at summary-mode storm scale retaining it
                # would be pure GC ballast (the counters still stamp).
                log_handoffs=(args.log == "full"),
            )
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        t_wall = time.perf_counter()
        try:
            result = fleet.run(reqs)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        wall_s = time.perf_counter() - t_wall
        s = result.summary()
        # Blame stamp (ISSUE 11): flat keys the fleet determinism gate
        # pins at exact equality, plus the `blame` summary record.
        bf = blame.summary_fields("fleet")
        s["blame_crc"] = bf["crc"]
        s["blame_quota_ticks"] = bf["quota_ticks"]
        for cat in CATEGORIES:
            s[f"blame_{cat}"] = bf["categories"][cat]
        metrics.log("blame", **bf)
        s["wall_s"] = round(wall_s, 3)
        s["wall_tokens_per_s"] = round(
            result.output_tokens / max(wall_s, 1e-9), 1)
        registry.set("serve.tokens_per_s", s["tokens_per_s"])
        registry.emit(metrics, mode="fleet", final=True)
        for rec in result.replica_log:
            metrics.log("replica", **rec)
        for rec in result.transport_log:
            metrics.log("transport", **rec)
        for ev in result.events:
            metrics.log("fault", **{"mode": "fleet", **ev})
        if metrics.jsonl_enabled and args.log == "full":
            # Handoff lifecycle records (ISSUE 13): full-log only —
            # at 10^5-storm scale one record per transfer state would
            # rival the tick volume the summary mode exists to avoid
            # (the gated summary counters cover the totals either way).
            for rec in result.handoff_log:
                metrics.log("handoff", **rec)
            for rec in result.request_records():
                metrics.log("request", **rec)
        # Alert totals are ALWAYS stamped (zero/empty-CRC without
        # --slo): the fleet determinism gate lists them, and a gated
        # metric must exist in every fleet-bench run. The stamp covers
        # every alert fired BEFORE the summary record itself — a rule
        # matching the `serve` event would fire after the stamp is
        # frozen (its record still lands in the JSONL, and `mctpu
        # health` judges the file, not this stamp). Identical-seed
        # runs freeze identically, so the determinism gate holds.
        from ..obs.alerts import alerts_crc

        s["alerts_fired"] = (len(alert_engine.alerts)
                             if alert_engine is not None else 0)
        s["alerts_crc"] = (alert_engine.crc if alert_engine is not None
                           else alerts_crc([]))
        metrics.log("serve", **{
            "bench": "fleet", "policy": args.policy,
            "autoscale": bool(args.autoscale),
            "redispatch": args.redispatch,
            "spec": args.spec, "spec_k": args.spec_k,
            "replicas_initial": (sum(pools.values()) if pools
                                 else args.replicas),
            "rate": args.rate,
            "slots": args.slots, "page_size": args.page_size,
            "pages": pages, "compute": args.compute,
            # Flight-recorder geometry flag (ISSUE 15): `mctpu replay`
            # rebuilds each replica's mirror with sharing on/off from it.
            "prefix_cache": bool(args.prefix_cache),
            # Host-tier geometry (ISSUE 17): the replay mirror extends
            # each replica's digest with the tier tuple iff > 0.
            "host_pages": host_pages,
            # Transport mode (ISSUE 20): the replay mirror folds the
            # per-tick transport block into fleet_digest iff enabled;
            # lease_ticks is the EFFECTIVE value (0 flag -> default).
            "transport": bool(args.transport),
            "lease_ticks": fleet.lease_ticks, **s,
        })
        print(json.dumps({"bench": "fleet", "compute": args.compute,
                          "policy": args.policy, **s}))
        print(json.dumps({
            "metric": "fleet_tokens_per_s", "value": s["tokens_per_s"],
            "unit": "tokens/s (fleet-clock)",
            "wall_s": s["wall_s"],
            "wall_tokens_per_s": s["wall_tokens_per_s"],
            "requests": len(result.requests),
            "replicas": result.replicas_final,
            "redispatches": result.redispatches,
            "trace_crc": result.trace_crc,
        }))
    return 0


if __name__ == "__main__":
    sys.exit(serve_bench_main())
