"""Runtime observability layer (ISSUE 6): MetricsRegistry aggregation,
per-request trace timelines, `mctpu top` frames, and the perf-regression
gate — all deterministic under faults.FakeClock.

THE acceptance tests live here:
- a seeded Poisson serve-bench run's tick trail reconstructs every
  request with a status-consistent lifecycle whose per-status counts
  match the engine's own terminal totals;
- `mctpu compare` exits 0 on identical runs and 1 on an injected >=10%
  tokens/s regression;
both driven end-to-end by a FakeClock (no wall-clock in any asserted
number), plus a golden byte-for-byte round-trip of `mctpu report` and
`mctpu trace` on the checked-in sample run (regenerate with
scripts/make_obs_sample.py after deliberate schema/render changes).
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax

from mpi_cuda_cnn_tpu.faults import FakeClock, FaultInjector, supervise
from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
from mpi_cuda_cnn_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    log_bucket_bounds,
    percentiles_from_record,
)
from mpi_cuda_cnn_tpu.obs.regress import (
    compare,
    compare_main,
    extract_metrics,
    infer_direction,
)
from mpi_cuda_cnn_tpu.obs.schema import (
    dump_records,
    load_records,
    make_record,
    validate_record,
)
from mpi_cuda_cnn_tpu.obs.timeline import reconstruct, trace_main
from mpi_cuda_cnn_tpu.obs.top import TopState, render, top_main
from mpi_cuda_cnn_tpu.serve.bench import make_workload
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
from mpi_cuda_cnn_tpu.utils.logging import MetricsLogger
from mpi_cuda_cnn_tpu.utils.profiling import StepTimer

REPO = Path(__file__).resolve().parents[1]
DATA = REPO / "tests" / "data"

MODEL = TransformerLM(vocab=13, dim=32, heads=4, depth=2, max_seq=48)


@pytest.fixture(scope="module")
def engine():
    params = MODEL.init(jax.random.key(0))
    # Pool far below the workload's worst case: preemption/requeue
    # lifecycles appear in the trail, not just the happy path.
    return PagedEngine(MODEL, params, slots=3, num_pages=10, page_size=4,
                       prefill_chunk=8, max_len=40)


# ------------------------------------------------- metrics primitives


def test_log_bucket_bounds_pure_and_ascending():
    b = log_bucket_bounds()
    assert b == log_bucket_bounds()  # pure function of its arguments
    assert all(x < y for x, y in zip(b, b[1:]))
    assert b[0] == pytest.approx(1e-2 * 10 ** 0.1)
    with pytest.raises(ValueError):
        log_bucket_bounds(lo=0.0)


def test_counter_monotonic_and_gauge_envelope():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge()
    g.set(5)
    g.set(1)
    g.set(3)
    assert (g.value, g.lo, g.hi) == (3.0, 1.0, 5.0)


def test_histogram_percentiles_and_roundtrip():
    h = Histogram()
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    assert h.count == 5 and h.min == 1.0 and h.max == 100.0
    # Percentile estimates are clamped to the exact observed envelope.
    assert h.percentile(0) == 1.0
    assert h.percentile(100) == 100.0
    assert 1.0 <= h.percentile(50) <= 4.0
    # Record round-trip: sparse buckets reconstruct identical counts.
    h2 = Histogram.from_fields(h.to_fields())
    assert h2.counts == h.counts and h2.count == h.count
    assert [h2.percentile(q) for q in (50, 95, 99)] == \
        [pytest.approx(h.percentile(q)) for q in (50, 95, 99)]
    assert h.percentile(50) is not None
    assert Histogram().percentile(50) is None


def test_registry_snapshot_is_schema_valid_and_fakeclock_stamped():
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    reg.inc("serve.decode_ticks", 3)
    reg.set("serve.queue_depth", 7)
    reg.observe("serve.ttft_ms", 12.5)
    reg.observe("serve.ttft_ms", None)  # null moments are skipped
    clock.advance(2.5)
    rec = reg.snapshot(mode="continuous")
    validate_record(rec)
    assert rec["event"] == "metrics" and rec["t"] == 2.5
    assert rec["counters"]["serve.decode_ticks"] == 3
    assert rec["gauges"]["serve.queue_depth"]["value"] == 7
    assert rec["histograms"]["serve.ttft_ms"]["count"] == 1
    p = percentiles_from_record(rec, "serve.ttft_ms")
    assert p["p50"] == pytest.approx(12.5)
    assert percentiles_from_record(rec, "absent")["p99"] is None


def test_registry_aggregation_deterministic_under_fake_clock():
    """The determinism contract: aggregation math never reads the
    clock, so two registries fed the same observations — under clocks
    advanced DIFFERENTLY — produce identical aggregate fields."""
    rega = MetricsRegistry(clock=FakeClock())
    fast = FakeClock()
    regb = MetricsRegistry(clock=fast)
    for i in range(100):
        fast.advance(1.0)  # only b's clock moves during aggregation
        for reg in (rega, regb):
            reg.inc("n")
            reg.set("depth", i % 7)
            reg.observe("lat_ms", float(i) * 1.7)
    assert json.dumps(rega.snapshot_fields()) == \
        json.dumps(regb.snapshot_fields())


def test_steptimer_and_metricslogger_accept_fake_clock(tmp_path):
    clock = FakeClock()
    timer = StepTimer(clock=clock)
    timer.start()
    with timer.phase("data"):
        clock.advance(0.010)
    with timer.phase("dispatch"):
        clock.advance(0.030)
    with timer.exclude():
        clock.advance(5.0)  # AOT compile must not pollute the envelope
    clock.advance(0.010)
    timer.stop(2)
    assert timer.total_s == pytest.approx(0.050)
    assert timer.mean_step_ms == pytest.approx(25.0)
    assert timer.phases_ms() == {"data": 5.0, "dispatch": 15.0,
                                 "other": 5.0}

    path = tmp_path / "r.jsonl"
    with MetricsLogger(path, echo=False, clock=clock) as metrics:
        clock.advance(1.5)
        metrics.log("train", step=1, loss=0.5)
    (rec,) = load_records(path)
    assert rec["t"] == 1.5  # stamped by the injected clock, exactly


# ------------------------------------- FakeClock serving e2e + trace


def _clock_serve(engine, mode, *, sink=None, registry=None):
    """One seeded Poisson serve run, fully FakeClock-driven (arrival
    waits and injected slow faults advance the clock; compute is
    instantaneous in clock time)."""
    clock = FakeClock()
    reqs = make_workload(n=8, vocab=13, prompt_min=4, prompt_max=8,
                         out_min=6, out_max=18, rate=40.0, seed=5,
                         deadline_s=0.35)
    faults = FaultInjector(
        "slow@serve.tick:10?s=0.15;slow@serve.tick:20?s=0.15;"
        "slow@serve.tick:30?s=0.15", clock=clock)
    res = engine.run(reqs, mode=mode, time_fn=clock,
                     sleep_fn=clock.advance, faults=faults,
                     registry=registry, tick_sink=sink)
    return res, clock


def _run_records(engine, modes=("static", "continuous")):
    """Records of a two-mode FakeClock run in serve-bench's layout
    (tick + metrics + request + serve events), plus per-mode results."""
    records, results = [], {}
    for mode in modes:
        ticks = []
        registry = MetricsRegistry(clock=FakeClock())
        res, clock = _clock_serve(engine, mode,
                                  sink=lambda r: ticks.append(r),
                                  registry=registry)
        results[mode] = res
        records += [make_record("tick", t["now"], **t) for t in ticks]
        s = res.summary()
        registry.set("serve.tokens_per_s", s["tokens_per_s"])
        records.append(registry.snapshot(mode=mode, final=True))
        records += [make_record("request", clock.now, **r)
                    for r in res.request_records()]
        records.append(make_record("serve", clock.now, **s))
    return records, results


def test_trace_reconstructs_every_request_consistently(engine, tmp_path):
    """THE trace acceptance: lifecycles derived purely from the tick
    trail agree with the engine's own request records — same terminal
    status per request, token counts accounted, and per-status totals
    equal to the engine's returned counts. Preempt/requeue cycles and
    expired requests are exercised (constrained pool + deadlines)."""
    records, results = _run_records(engine)
    assert results["continuous"].preemptions > 0  # requeues exercised
    by_mode = reconstruct(records)
    for mode, res in results.items():
        lifecycles = by_mode[mode]
        assert len(lifecycles) == len(res.requests)
        assert all(lc.consistent for lc in lifecycles.values()), [
            (rid, lc.derived_status, lc.record.get("status"))
            for rid, lc in lifecycles.items() if not lc.consistent
        ]
        derived = {}
        for lc in lifecycles.values():
            derived[lc.derived_status] = derived.get(lc.derived_status,
                                                     0) + 1
        assert derived == res.status_counts()
        # Tick-derived token accounting matches each record exactly.
        for lc in lifecycles.values():
            assert lc.tokens_accounted == lc.record["output_tokens"]

    path = tmp_path / "run.jsonl"
    dump_records(records, path)
    assert trace_main([str(path)]) == 0
    assert trace_main([str(path), "--request", "2", "--mode",
                       "continuous"]) == 0
    assert trace_main([str(path), "--format", "json"]) == 0


def test_trace_flags_engine_telemetry_drift(engine, tmp_path):
    """Tampering with the trail (a dropped decode tick) must exit
    nonzero: the reconstruction is a cross-check, not a rendering."""
    records, _ = _run_records(engine, modes=("continuous",))
    tampered = []
    dropped = False
    for r in records:
        if not dropped and r["event"] == "tick" and r.get("decoded"):
            r = {**r, "decoded": r["decoded"][1:]}
            dropped = True
        tampered.append(r)
    assert dropped
    path = tmp_path / "bad.jsonl"
    dump_records(tampered, path)
    assert trace_main([str(path)]) == 1


def test_tick_records_stream_and_are_never_retained(engine):
    """Tick records flow to the sink as they happen (the JSONL is the
    tick store); ServeResult retains none — an in-memory tick list
    would grow without bound on a long-lived serve. A bare run (no
    registry, no sink) skips building them entirely."""
    ticks = []
    res, _ = _clock_serve(engine, "continuous", sink=ticks.append)
    assert ticks and "ticks" not in vars(res)
    res2, _ = _clock_serve(engine, "continuous")  # bare run still lands
    assert res2.status_counts() == res.status_counts()


def test_gantt_marks_queue_and_preempt_waits_for_focused_request():
    """The --request legend: queue time before first admission renders
    'q', preempted-waiting before readmission renders 'x', both on the
    row of the slot the request next occupies; activity still wins
    inside a column."""
    from mpi_cuda_cnn_tpu.obs.timeline import render_gantt

    def tick(i, **kw):
        return {"event": "tick", "tick": i, "now": round(0.1 * i, 4),
                "mode": "continuous", "queue": 0, "free_pages": 9, **kw}

    records = [
        make_record("request", 1.0, id=7, mode="continuous",
                    status="finished", prompt_tokens=4, output_tokens=2,
                    ttft_ms=1.0, latency_ms=2.0, arrival_s=0.0,
                    queue_wait_ms=100.0, preemptions=1),
        tick(0),                                     # queued (arrival 0)
        tick(1, admitted=[[0, 7]], prefill=[0, 7, 4]),
        tick(2, preempted=[7]),                      # requeued, waiting
        tick(3),
        tick(4, admitted=[[0, 7]], prefill=[0, 7, 4]),
        tick(5, decoded=[[0, 7]], finished=[7]),
    ]
    g = render_gantt(records, "continuous", rid=7)
    assert g.splitlines()[-1] == "slot  0 |qPxxPD"


def test_serve_registry_deterministic_across_runs(engine):
    """Two FakeClock runs of the identical workload produce bitwise-
    identical registry snapshots — the property the regression gate
    stands on (identical runs MUST compare clean)."""
    snaps = []
    for _ in range(2):
        registry = MetricsRegistry(clock=FakeClock())
        _clock_serve(engine, "continuous", registry=registry)
        snaps.append(json.dumps(registry.snapshot_fields()))
    assert snaps[0] == snaps[1]


# --------------------------------------------- perf-regression gate


def test_compare_passes_identical_and_gates_injected_regression(
        engine, tmp_path, capsys):
    """THE gate acceptance: identical FakeClock runs exit 0; scaling
    the candidate's tokens/s down 12% (past the 10% tolerance) exits 1
    and names the regressed metric."""
    records, _ = _run_records(engine)
    base, cand = tmp_path / "base.jsonl", tmp_path / "cand.jsonl"
    dump_records(records, base)
    dump_records(records, cand)
    assert compare_main([str(base), str(cand)]) == 0

    slowed = []
    for r in records:
        if r["event"] == "serve":
            r = {**r, "tokens_per_s": round(r["tokens_per_s"] * 0.88, 2)}
        slowed.append(r)
    dump_records(slowed, cand)
    capsys.readouterr()
    assert compare_main([str(base), str(cand)]) == 1
    err = capsys.readouterr().err
    assert "REGRESSION" in err and "tokens_per_s" in err


def test_compare_gate_file_rules(engine, tmp_path):
    """--gate thresholds: only listed metrics gate, per-metric
    tolerance applies, and a listed metric missing from either side is
    itself a failure (silently vanishing metrics rot gates)."""
    records, _ = _run_records(engine, modes=("continuous",))
    base, cand = tmp_path / "base.jsonl", tmp_path / "cand.jsonl"
    dump_records(records, base)
    slowed = [
        {**r, "tokens_per_s": r["tokens_per_s"] * 0.8}
        if r["event"] == "serve" else r
        for r in records
    ]
    dump_records(slowed, cand)
    gate = tmp_path / "gate.json"
    # Tolerant gate: a 20% drop passes at tol 30.
    gate.write_text(json.dumps({"metrics": {
        "serve.continuous.tokens_per_s": {"tol_pct": 30,
                                          "direction": "higher"},
        "serve.continuous.decode_ticks": {"tol_pct": 0},
    }}))
    assert compare_main([str(base), str(cand), "--gate", str(gate)]) == 0
    # Strict gate: the same drop fails at tol 10.
    gate.write_text(json.dumps({"metrics": {
        "serve.continuous.tokens_per_s": {"tol_pct": 10,
                                          "direction": "higher"},
    }}))
    assert compare_main([str(base), str(cand), "--gate", str(gate)]) == 1
    # A gated metric absent from both sides fails loudly.
    gate.write_text(json.dumps({"metrics": {"no.such.metric": {}}}))
    assert compare_main([str(base), str(cand), "--gate", str(gate)]) == 1


def test_compare_rejects_undirectioned_gate_and_vacuous_runs(
        engine, tmp_path, capsys):
    """Two gate-rot guards: an explicitly gated metric whose direction
    is neither specified nor name-inferable is a config error (not a
    silent demotion to info), and a compare where NOTHING ends up gated
    exits nonzero instead of vacuously green."""
    with pytest.raises(ValueError, match="direction"):
        compare({"serve.continuous.requests": 12.0},
                {"serve.continuous.requests": 5.0},
                {"metrics": {"serve.continuous.requests": {"tol_pct": 0}}})
    records, _ = _run_records(engine, modes=("continuous",))
    base, cand = tmp_path / "base.jsonl", tmp_path / "cand.jsonl"
    dump_records(records, base)
    dump_records(records, cand)
    gate = tmp_path / "gate.json"
    gate.write_text(json.dumps({"metrics": {
        "serve.continuous.requests": {"tol_pct": 0}}}))
    assert compare_main([str(base), str(cand), "--gate", str(gate)]) == 2
    assert "direction" in capsys.readouterr().err
    gate.write_text(json.dumps({"metrics": {}}))  # empty gate: error
    assert compare_main([str(base), str(cand), "--gate", str(gate)]) == 2
    # No gate + no shared direction-inferable metric: nothing gated.
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps({"metric": "thing", "value": 1.0}))
    b.write_text(json.dumps({"metric": "thing", "value": 9.0}))
    capsys.readouterr()
    assert compare_main([str(a), str(b)]) == 2
    assert "no metric was gated" in capsys.readouterr().err


def test_compare_direction_inference_and_trajectory():
    assert infer_direction("serve.continuous.tokens_per_s") == "higher"
    assert infer_direction("serve.static.ttft_p99_ms") == "lower"
    assert infer_direction("epoch.last_s") == "lower"
    assert infer_direction("train.last_step") is None
    # Directional evaluation: a big drop in a higher-is-better metric
    # regresses; the same move in an unknown-direction metric is info.
    rows, bad = compare({"a.tokens_per_s": 100.0, "b": 1.0},
                        {"a.tokens_per_s": 80.0, "b": 5.0})
    assert bad == ["a.tokens_per_s"]
    assert [r["verdict"] for r in rows] == ["REGRESS", "info"]


def test_compare_reads_driver_captures(tmp_path):
    """Driver-capture JSON (one object: cmd, rc, tail, parsed) is a
    first-class compare input, and three or more files are a trajectory
    (last file = candidate vs directional best of the earlier ones).
    Failed captures (rc != 0, parsed null) contribute nothing rather
    than zeros. Reads the three-file fixture tests/data/
    driver_capture_*.json — made-up values in the driver's shape."""
    paths = sorted(str(p) for p in DATA.glob("driver_capture_*.json"))
    assert len(paths) == 3
    m = extract_metrics(paths[0])
    assert m["mnist_epoch_wallclock"] == 0.5
    assert extract_metrics(paths[1]) == {}  # rc=124 capture: no metrics
    gate = tmp_path / "gate.json"
    gate.write_text(json.dumps({"metrics": {
        "mnist_epoch_wallclock": {"tol_pct": 40, "direction": "lower"},
    }}))
    assert compare_main(paths + ["--gate", str(gate)]) == 0
    gate.write_text(json.dumps({"metrics": {
        "mnist_epoch_wallclock": {"tol_pct": 5, "direction": "lower"},
    }}))
    assert compare_main(paths + ["--gate", str(gate)]) == 1  # 0.50 -> 0.55


def test_compare_reads_stamped_bench_script_output(tmp_path):
    """bench_decode/bench_speculative-style stdout (per-config lines +
    a schema-stamped headline record) parses into gateable metrics."""
    out = tmp_path / "decode.jsonl"
    out.write_text(
        json.dumps({"bench": "lm_decode", "kv_heads": 2,
                    "decode_tokens_per_s": 900}) + "\n"
        + json.dumps(make_record(
            "bench", 12.3, metric="decode_tokens_per_s", value=1000.0,
            unit="tokens/s", config="kv2", plain_tokens_per_s=800.0,
            backend="cpu")) + "\n"
    )
    m = extract_metrics(out)
    assert m["decode_tokens_per_s"] == 1000.0
    assert m["decode_tokens_per_s.plain_tokens_per_s"] == 800.0


# ------------------------------------------------ golden round-trip


def test_sample_run_is_schema_pinned():
    """Every record of the checked-in sample validates strictly, and
    the event families it exercises are exactly the serving set — a
    schema/event-family drift fails here first, loudly."""
    records = load_records(DATA / "sample_serve_run.jsonl", strict=True)
    assert {r["event"] for r in records} == \
        {"tick", "metrics", "request", "fault", "serve", "alert", "blame"}
    # The diversity the goldens depend on: preemptions AND expiries.
    assert any(r["event"] == "tick" and r["preempted"] for r in records)
    # ISSUE 11's additions: causal tick fields (arrival announcements,
    # blocker edges, preemption beneficiaries) and a conserved `blame`
    # summary per mode.
    assert any(r["event"] == "tick" and r.get("blocked") for r in records)
    assert any(r["event"] == "tick" and r.get("preempted_for")
               for r in records)
    assert all("arrived" in r for r in records if r["event"] == "tick")
    assert all(r.get("conserved") for r in records
               if r["event"] == "blame")
    assert any(r["event"] == "request" and r.get("status") == "expired"
               for r in records)
    # ISSUE 8's additions: a tenant mix, per-tick terminal detail, and
    # a live alert trail with both staleness and burn-rate kinds.
    assert {r.get("tenant") for r in records
            if r["event"] == "request"} == {"t0", "t1"}
    assert any(r["event"] == "tick" and r.get("terminal") for r in records)
    assert {r["kind"] for r in records if r["event"] == "alert"} == \
        {"absence", "burn_rate"}


def test_golden_report_roundtrip(monkeypatch, capsys):
    """`mctpu report` output on the sample run is byte-for-byte the
    checked-in golden (regenerate via scripts/make_obs_sample.py)."""
    from mpi_cuda_cnn_tpu.obs.report import report_main

    monkeypatch.chdir(REPO)
    assert report_main(["tests/data/sample_serve_run.jsonl"]) == 0
    assert capsys.readouterr().out == \
        (DATA / "golden_serve_report.md").read_text()


def test_golden_trace_roundtrip(monkeypatch, capsys):
    monkeypatch.chdir(REPO)
    assert trace_main(["tests/data/sample_serve_run.jsonl",
                       "--width", "80"]) == 0
    assert capsys.readouterr().out == \
        (DATA / "golden_serve_trace.md").read_text()


def test_golden_health_roundtrip(monkeypatch, capsys):
    """`mctpu health` on the sample run is byte-for-byte the golden —
    and exits 1: the sample's SLO spec is violated BY DESIGN (the
    golden must show both ok and VIOLATED verdicts)."""
    from mpi_cuda_cnn_tpu.obs.health import health_main

    monkeypatch.chdir(REPO)
    assert health_main(["tests/data/sample_serve_run.jsonl",
                        "--slo", "tests/data/sample_slo.json",
                        "--verify-alerts"]) == 1
    assert capsys.readouterr().out == \
        (DATA / "golden_serve_health.md").read_text()


def test_trace_tenant_filter(monkeypatch, capsys):
    """--tenant restricts the request table to one tenant's rows."""
    monkeypatch.chdir(REPO)
    assert trace_main(["tests/data/sample_serve_run.jsonl",
                       "--tenant", "t1", "--mode", "continuous"]) == 0
    out = capsys.readouterr().out
    assert "| t1 |" in out and "| t0 |" not in out


# ------------------------------------------------------- mctpu top


def test_top_once_frame_renders_engine_and_counts(capsys):
    assert top_main([str(DATA / "sample_serve_run.jsonl"), "--once"]) == 0
    out = capsys.readouterr().out
    assert "ENGINE [continuous]" in out and "ENGINE [static]" in out
    assert "ttft" in out and "tok/s" in out
    # ALERTS panel (ISSUE 8): the sample's live alert trail renders.
    assert "ALERTS" in out and "tick-stale" in out
    assert "\x1b" not in out  # --once is pipe/CI safe: no ANSI codes


def test_top_state_ingest_and_render_train():
    state = TopState()
    reg = MetricsRegistry(clock=FakeClock())
    reg.inc("train.steps", 50)
    reg.inc("train.heartbeats")
    reg.observe("train.step_ms", 20.0)
    state.ingest(reg.snapshot())
    state.ingest(make_record("train", 1.0, step=50, loss=0.5))
    state.ingest(make_record("epoch", 2.0, epoch=0, seconds=2.0))
    frame = render(state, "live.jsonl")
    assert "TRAIN" in frame and "heartbeats 1" in frame
    assert "step ms p50/p95/p99" in frame
    assert top_main(["/nonexistent/x.jsonl", "--once"]) == 2


# ------------------------------------------- report merge + trainers


def test_report_merge_combines_segments(tmp_path, capsys):
    """--merge renders one report over many files/run segments — the
    supervisor pre/post-restart view as a single table."""
    from mpi_cuda_cnn_tpu.obs.report import report_main

    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    clock = FakeClock()
    with MetricsLogger(a, echo=False, clock=clock) as m:
        m.log("train", step=1, loss=2.0)
        m.log("epoch", epoch=0, seconds=1.0)
    with MetricsLogger(b, echo=False, clock=clock) as m:
        m.log("train", step=2, loss=1.0)
        m.log("epoch", epoch=1, seconds=3.0)
    assert report_main(["--merge", "--format", "json",
                        str(a), str(b)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["segments"] == 2
    assert out["train"]["last_loss"] == 1.0  # later file's record wins
    assert out["epochs"]["count"] == 2  # epochs from BOTH segments
    assert report_main(["--merge", str(a), str(b)]) == 0


def test_report_merge_folds_registry_snapshots_across_segments(
        tmp_path, capsys):
    """Each relaunched process's registry restarts at zero, so --merge
    must SUM counters and merge histograms across segment-latest
    snapshots — last-snapshot-wins would report only the post-restart
    segment's totals (the exact supervisor view --merge exists for).
    Gauges stay last-segment-wins."""
    from mpi_cuda_cnn_tpu.obs.report import report_main

    a, b = tmp_path / "crashed.jsonl", tmp_path / "resumed.jsonl"
    for path, steps, ms, tps in ((a, 60, [5.0, 7.0], 100.0),
                                 (b, 40, [9.0], 200.0)):
        reg = MetricsRegistry(clock=FakeClock())
        reg.inc("train.steps", steps)
        for v in ms:
            reg.observe("train.step_ms", v)
        reg.set("train.tokens_per_s", tps)
        with MetricsLogger(path, echo=False, clock=FakeClock()) as m:
            # Two snapshots per segment: within a segment the newest
            # subsumes the older (cumulative registry) — only across
            # segments does folding kick in.
            reg.emit(m)
            reg.inc("train.heartbeats")
            reg.emit(m)
    assert report_main(["--merge", "--format", "json",
                        str(a), str(b)]) == 0
    got = json.loads(capsys.readouterr().out)["metrics"]["train"]
    assert got["counters"]["train.steps"] == 100  # 60 + 40, not 40
    assert got["counters"]["train.heartbeats"] == 2  # 1 per segment
    assert got["histograms"]["train.step_ms"]["count"] == 3
    assert got["histograms"]["train.step_ms"]["min"] == 5.0
    assert got["histograms"]["train.step_ms"]["max"] == 9.0
    assert got["gauges"]["train.tokens_per_s"] == 200.0  # last segment


def test_trainer_threads_registry_and_emits_metrics_events(tmp_path):
    """The CNN trainer's epoch fold: steps counter, step-time
    histogram, samples/s gauge, heartbeats — snapshotted as
    schema-valid `metrics` events in the run file."""
    from mpi_cuda_cnn_tpu.data.datasets import synthetic_stripes
    from mpi_cuda_cnn_tpu.models.presets import get_model
    from mpi_cuda_cnn_tpu.train.trainer import Trainer
    from mpi_cuda_cnn_tpu.utils.config import Config

    path = tmp_path / "run.jsonl"
    ds = synthetic_stripes(num_train=128, num_test=32)
    cfg = Config(model="reference_cnn", epochs=2, batch_size=32,
                 log_every=0, eval_every=0, num_devices=1)
    reg = MetricsRegistry(clock=FakeClock())
    with MetricsLogger(path, echo=False) as metrics:
        Trainer(get_model("reference_cnn"), ds, cfg, metrics=metrics,
                registry=reg).train()
    assert reg.counters["train.steps"].value == 2 * (128 // 32)
    assert reg.counters["train.heartbeats"].value == 2
    assert reg.histograms["train.step_ms"].count == 2
    assert reg.gauges["train.samples_per_s"].value > 0
    snaps = [r for r in load_records(path, strict=True)
             if r["event"] == "metrics"]
    assert len(snaps) == 2  # one snapshot per epoch
    assert snaps[-1]["counters"]["train.steps"] == 8


def test_supervise_counts_restarts_in_registry(tmp_path):
    reg = MetricsRegistry(clock=FakeClock())
    calls = []

    def attempt(n):
        calls.append(n)
        if n < 2:
            raise RuntimeError("boom")
        return "ok"

    with MetricsLogger(tmp_path / "s.jsonl", echo=False) as metrics:
        out = supervise(attempt, max_restarts=3, metrics=metrics,
                        registry=reg, backoff_base=0, sleep=lambda _: None)
    assert out == "ok" and calls == [0, 1, 2]
    assert reg.counters["train.restarts"].value == 2
    faults = [r for r in load_records(tmp_path / "s.jsonl")
              if r["event"] == "fault"]
    assert [f["kind"] for f in faults] == ["restart", "restart"]
