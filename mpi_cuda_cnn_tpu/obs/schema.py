"""The one JSONL record shape every metrics producer shares.

Before this module, each emitter invented its own dialect: MetricsLogger
wrote {"event", "t", ...}, bench.py printed a one-off benchmark object,
scripts/profile_*.py printed ad-hoc rows, and a hand-appended capture
file mixed all three plus `# comment` lines. PERF.md tables were then
assembled by hand from the union. One schema ends that: every record carries a
version stamp and an event name, event families declare their required
keys, and `iter_records`/`validate_record` are the single read/check
path used by the `mctpu report` aggregator, the tests, and any future
consumer.

Records are one JSON object per line. Lines starting with '#' are
comments (the run-boundary marker MetricsLogger writes) and are skipped
by the reader.
"""

from __future__ import annotations

import json
from pathlib import Path
from collections.abc import Iterable, Iterator

SCHEMA_VERSION = 1

# Keys every record must carry. "t" is seconds since the producer
# started (relative, so records from different processes don't need
# clock agreement); "event" names the record family.
REQUIRED_KEYS = ("schema", "event", "t")

# Per-family required keys (beyond REQUIRED_KEYS). Families not listed
# here are free-form — the schema constrains what the report aggregator
# depends on, not what producers may add.
EVENT_KEYS: dict[str, tuple[str, ...]] = {
    # What ran (utils/backend.device_stamp): the first record of every
    # jax-using entry point, so a CPU run can never be read as a chip
    # run. "mesh" is {axis: size} for trainers, null for serving.
    "device": ("platform", "device_kind", "device_count", "mesh"),
    # Training progress (per log interval). "step" is the in-run step.
    "train": ("step", "loss"),
    # Epoch wall-clock (CNN trainer).
    "epoch": ("epoch", "seconds"),
    # Eval sweep result.
    "eval": (),
    # Step-phase wall-clock attribution: milliseconds per step spent in
    # host-side data prep, async dispatch, device compute wait, and
    # checkpointing, over `steps` steps.
    "step_phases": ("steps", "phases_ms"),
    # Compiled-program accounting from XLA cost analysis: FLOPs and
    # bytes per dispatched program, plus HLO collective counts.
    "program": ("flops", "collectives"),
    # Device memory telemetry (per-device bytes; absent stats -> null).
    "memory": ("devices",),
    # Host-side span (obs.trace.span): nested name and duration.
    "span": ("name", "ms"),
    # One served request (serve/engine.py): latency from arrival to
    # first token (ttft_ms) and to completion (latency_ms). Aborted
    # requests carry null where the moment never happened; "status" is
    # the terminal status (finished/expired/cancelled/rejected/failed)
    # — absent in pre-ISSUE-4 records, treated as "finished".
    # "tenant" (ISSUE 8) is the traffic-class identity SLO accounting
    # buckets by — absent in pre-ISSUE-8 records, treated as "default".
    "request": ("id", "mode", "prompt_tokens", "output_tokens",
                "ttft_ms", "latency_ms"),
    # One serving-bench run summary per scheduler mode (serve/bench.py).
    "serve": ("mode", "requests", "tokens_per_s"),
    # One fault-domain occurrence (faults.py / trainers / serve engine):
    # injected faults (kind="injected_*"), supervisor restarts, NaN-guard
    # actions (nonfinite_step / nan_restore), checkpoint fallbacks,
    # preemptions (kind="preempt") and cross-resume topology changes
    # (kind="topology_change" — ISSUE 5), request aborts/rejections,
    # watchdog breaches. Free-form beyond "kind" — the robustness table
    # aggregates by kind.
    "fault": ("kind",),
    # One checkpoint lifecycle moment (trainers, ISSUE 5): "reason" is
    # why it happened (preempt = the preemption snapshot, resume = a
    # restore into a fresh process); "step" is the global step it
    # captures. Interval saves stay un-evented (they'd dominate the
    # stream); the elasticity-relevant moments are what reports need.
    "ckpt": ("step", "reason"),
    # One MetricsRegistry snapshot (obs/metrics.py, ISSUE 6): aggregated
    # counters (monotonic totals), gauges ({value, lo, hi}), and
    # log-bucket histograms ({count, sum, min, max, buckets: [[i, n]]}
    # over obs.metrics.log_bucket_bounds edges). `mctpu top` tails
    # these; `mctpu compare` gates their named values.
    "metrics": ("counters", "gauges", "histograms"),
    # One fleet-router iteration (serve/fleet.py, ISSUE 7): healthy
    # replica count, undispatched backlog, this tick's routing moments
    # (dispatched/redispatched rids) and the per-replica load map
    # {name: [queue, running, free_pages]} the dispatch policy reads.
    # Causality (ISSUE 11): "arrived" (rids whose arrival fell due this
    # tick) and "failed_over" ([[rid, replica]] — requests a failover
    # stranded, ending their active blame segment at the crash).
    # Disaggregation (ISSUE 13): "handoff_started" ([[rid, src]]),
    # "handoff_done" ([[rid, dst]]), "handoff_aborted" ([[rid, reason]])
    # and "handoffs_inflight" — the prefill->decode KV transfer markers,
    # ordered in the JSONL before any replica record of the same tick.
    # Lossy transport (ISSUE 20, --transport only): "transport" (the
    # bus's pre-step counter/link/partition block the replay mirror
    # folds into fleet_digest), "t_delivered" ([[rid, replica]] —
    # dispatches DELIVERED over the wire this tick, distinct from
    # dispatched_to which marks the router's send), "t_terminal"
    # (terminal details harvested from bus messages between ticks),
    # "t_retransmits" ([[kind, dst, rid]]) and "lease_refused"
    # ([[rid, replica]] — commits a replica refused past its lease).
    "fleet": ("tick", "now", "replicas"),
    # One transport-bus lifecycle moment (serve/transport.py, ISSUE 20):
    # kind is partition_open / partition_heal; "name" the isolated
    # replica, "tick"/"heal" the window. Message-level faults stay
    # un-evented as records (they'd rival the tick volume) — the
    # per-tick fleet "transport" block carries the counters.
    "transport": ("kind",),
    # One prefill->decode KV handoff lifecycle moment (serve/fleet.py,
    # ISSUE 13): "state" is started / done / aborted (aborted carries
    # "reason": sender_dead / receiver_dead / dropped / kv_corrupt /
    # decode_pool_empty / cancelled); "src"/"dst" the replica names,
    # "pages" the transfer size, "hid" the handoff sequence number the
    # fleet.handoff fault site triggers on.
    "handoff": ("rid", "state"),
    # One replica lifecycle moment (serve/fleet.py, ISSUE 7): kind is
    # join / crash / dead / restart_scheduled / restart / circuit_open
    # / leave / drain_complete — plus, for disaggregated fleets
    # (ISSUE 13), degraded / restored, whose "name" is the POOL
    # ("prefill"/"decode"), not a replica. Free-form beyond
    # (name, kind) — the fleet report table aggregates by kind per
    # name.
    "replica": ("name", "kind"),
    # One serving-engine scheduler iteration (serve/engine.py, ISSUE 6):
    # the per-tick state `mctpu trace` reconstructs request lifecycles
    # from — queue depth, free pages, and the tick's scheduling moments
    # (admitted [[slot, rid]], prefill [slot, rid, n] | null, decoded
    # [[slot, rid]], finished/preempted/failed rids, aborted
    # [[rid, status]]). "now" is seconds since run start on the
    # engine's (injectable) clock. "terminal" (ISSUE 8) details each
    # request reaching a terminal status this tick ({id, tenant,
    # status, ttft_ms, tpot_ms, queue_wait_ms}) — the streaming
    # good/bad events the SLO burn-rate rules fold. Prefix-sharing
    # runs (ISSUE 9) additionally carry "prefix_hits"
    # ([[rid, matched_tokens]] — the lifecycle marker `mctpu trace`
    # renders) and "prefix" ({shared_pages, retained_pages, hits,
    # misses, hit_tokens, cow_copies, inserts, evictions} — the
    # `mctpu top` cache panel). Causality (ISSUE 11): "arrived" (rids
    # whose arrival fell due this tick — the blame span's anchor),
    # "blocked" ([[rid, reason, holders]] — admission attempts that
    # failed, reason "pages"/"slots"/"quota", holders the occupying
    # rids: the blocker edges `mctpu explain` blames queue waits on),
    # and "preempted_for" ([[victim, beneficiary]] — whose page need
    # forced each eviction). Speculative runs (ISSUE 14) carry "spec"
    # ([[rid, proposed, accepted]] per slot round — a spec decode tick
    # commits 1 + accepted tokens for its rid, which is how `mctpu
    # trace` keeps the token cross-check exact under variable-length
    # commits).
    "tick": ("tick", "now", "queue", "free_pages"),
    # One benchmark headline (bench.py, scripts/bench_decode.py,
    # scripts/bench_speculative.py): "metric" names the measured
    # quantity, "value" its number (null when the capture failed —
    # bench.py's error line still stamps the family), "unit" its unit.
    # `mctpu compare` reads these as dotted `bench.*` metrics. This
    # family was emitted unregistered for three PRs — the exact drift
    # class `mctpu lint` MCT005 now catches at the call site.
    "bench": ("metric", "value", "unit"),
    # One causal-blame summary per mode (obs/causal.py, ISSUE 11):
    # aggregate per-category tick totals ("categories": self_compute /
    # queued_behind / preempted_by / redispatch_replay / router_wait —
    # each request's categories sum bitwise to its end-to-end tick
    # span), per-tenant breakdown ("tenants"), the quota skip-over
    # share ("quota_ticks"), and "crc" — the canonical per-request
    # blame CRC the fleet determinism gate pins at exact equality.
    "blame": ("mode", "requests", "categories"),
    # One SLO-attained goodput measurement (obs/goodput.py, ISSUE 16):
    # "kind" is run (one measured run) / candidate (one topology inside
    # an `mctpu autosize` sweep) / frontier (the sweep's folded
    # goodput-frontier summary + recommendation). run/candidate records
    # carry the Goodput.fields() block (requests, good, duration_s,
    # chips, goodput_rps, per_chip_rps, good_fraction, estimated,
    # thresholds); candidates add their topology spelling + the
    # underlying storm's trace/blame/state CRCs (unchanged by the sweep
    # harness — pinned by test); the frontier adds evaluated/pruned
    # counts, the ranked candidate order, and frontier_crc /
    # recommendation_crc — the numbers the autosize determinism gate
    # pins at 0%/equal.
    "goodput": ("kind",),
    # One chaos-search result (chaos/, ISSUE 19): "kind" is episode
    # (one sampled fault-schedule episode: its --fault-plan spelling,
    # axes label, violation check names, replay tick coverage, and the
    # trace/state/blame/episode CRCs the chaos determinism gate pins
    # at exact equality) / summary (the whole search: episode and
    # violation counts, the folded episodes_crc chain, and — on a
    # failing search — the ddmin-minimized plan + probe count).
    "chaos": ("kind",),
    # One fired alert (obs/alerts.py, ISSUE 8): "rule" names the rule
    # instance, "kind" its class (threshold / rate_of_change / absence
    # / burn_rate), "seq" its position in the run's alert sequence
    # (obs.alerts.alerts_crc pins the whole sequence as one number),
    # "at" the triggering record's timeline stamp; context beyond that
    # is free-form per kind (tenant/metric/burn for burn_rate,
    # field/value/threshold for threshold, family/gap_s for absence).
    "alert": ("seq", "rule", "kind", "severity", "at"),
}


def make_record(event: str, t: float, **fields) -> dict:
    """Assemble a schema-stamped record (does not validate — producers
    that want the check call validate_record on the result)."""
    return {"schema": SCHEMA_VERSION, "event": event, "t": round(t, 4),
            **fields}


def validate_record(rec: dict) -> dict:
    """Check one record against the schema; returns it unchanged.

    Raises ValueError naming every missing key — the error message is
    the schema documentation a producer sees first.
    """
    if not isinstance(rec, dict):
        raise ValueError(f"record must be an object, got {type(rec).__name__}")
    missing = [k for k in REQUIRED_KEYS if k not in rec]
    if missing:
        raise ValueError(f"record missing required keys {missing}: {rec}")
    if not isinstance(rec["schema"], int):
        raise ValueError(f"record schema must be an int: {rec['schema']!r}")
    if rec["schema"] > SCHEMA_VERSION:
        raise ValueError(
            f"record schema v{rec['schema']} is newer than this reader "
            f"(v{SCHEMA_VERSION})"
        )
    extra = EVENT_KEYS.get(rec["event"], ())
    missing = [k for k in extra if k not in rec]
    if missing:
        raise ValueError(
            f"{rec['event']!r} record missing keys {missing}: {rec}"
        )
    return rec


# Comment prefix MetricsLogger writes on each open — the run boundary
# in an append-mode file (iter_runs splits on it; iter_records skips it
# like any other comment).
RUN_MARKER = "# run"


def iter_records(path: str | Path, *, strict: bool = False) -> Iterator[dict]:
    """Yield records from a JSONL file, skipping blank and '#' lines.

    Pre-schema records (no "schema" key) are passed through unvalidated
    unless strict=True — report must keep reading pre-schema capture
    files.
    """
    for _, rec in _iter_lines(path, strict=strict):
        if rec is not None:
            yield rec


def iter_runs(path: str | Path, *, strict: bool = False) -> Iterator[list[dict]]:
    """Yield one record list per run, split at RUN_MARKER comment lines
    (append-mode files accumulate runs; aggregating across them would
    blend unrelated numbers). A file with no markers is one run."""
    current: list[dict] = []
    seen_any = False
    for is_marker, rec in _iter_lines(path, strict=strict):
        if is_marker:
            if current or seen_any:
                yield current
                current = []
            seen_any = True
        elif rec is not None:
            current.append(rec)
    if current or not seen_any:
        yield current


def _iter_lines(path: str | Path, *, strict: bool):
    """(is_run_marker, record | None) per line, shared by the readers."""
    with Path(path).open() as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if line.startswith(RUN_MARKER):
                yield True, None
                continue
            if not line or line.startswith("#"):
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                if strict:
                    raise ValueError(f"{path}:{lineno}: bad JSON: {e}") from e
                continue
            if strict or (isinstance(rec, dict) and "schema" in rec):
                validate_record(rec)
            yield False, rec


def load_records(path: str | Path, *, strict: bool = False) -> list[dict]:
    return list(iter_records(path, strict=strict))


def dump_records(records: Iterable[dict], path: str | Path) -> None:
    """Write records as JSONL (the round-trip twin of load_records)."""
    with Path(path).open("w") as fh:
        for rec in records:
            fh.write(json.dumps(rec) + "\n")


def fmt_cell(v, prec: int = 6) -> str:
    """The one table-cell formatter every obs renderer (report, trace,
    top, compare) shares: None is an em-dash (a moment that never
    happened), floats render at `prec` significant digits, dicts as
    sorted k:v pairs. Golden-output tests pin this formatting — change
    it here and every renderer moves together."""
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:.{prec}g}"
    if isinstance(v, dict):
        return ", ".join(f"{k}:{n}" for k, n in sorted(v.items())) or "—"
    return str(v)
