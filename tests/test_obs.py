"""Telemetry subsystem (obs/) tests.

Coverage per the subsystem's contract: cost-analysis FLOPs within
tolerance of a hand count on a tiny dense model, collective-count
extraction on a 2-device CPU-mesh psum step, JSONL schema round-trip,
a memory_stats smoke that skips cleanly on backends without allocator
stats, the StepTimer guard rails, and the MetricsLogger context
manager. The trainer-integration test drives the real CLI path the
acceptance criterion names.
"""

import json

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from mpi_cuda_cnn_tpu import obs
from mpi_cuda_cnn_tpu.parallel.mesh import make_mesh
from mpi_cuda_cnn_tpu.utils.logging import MetricsLogger
from mpi_cuda_cnn_tpu.utils.profiling import StepTimer


# ---------------------------------------------------------------- cost


def test_cost_analysis_flops_match_hand_count():
    """XLA's flop count for one dense matmul must agree with the
    hand-derived 2*M*K*N within tolerance (the tolerance absorbs
    epsilon ops XLA counts around the dot)."""
    m, k, n = 32, 64, 128
    f = jax.jit(lambda x, w: jnp.dot(x, w))
    x = jnp.ones((m, k), jnp.float32)
    w = jnp.ones((k, n), jnp.float32)
    costs = obs.analyze(f, x, w)
    assert costs.flops is not None
    hand = 2 * m * k * n
    assert abs(costs.flops - hand) / hand < 0.1, (costs.flops, hand)
    assert costs.bytes_accessed and costs.bytes_accessed > 0


def test_cost_analysis_scales_with_batch():
    """Twice the batch must cost ~twice the FLOPs — the property that
    makes cost analysis usable as an MFU numerator."""
    f = jax.jit(lambda x, w: jnp.dot(x, w))
    w = jnp.ones((64, 64), jnp.float32)
    c1 = obs.analyze(f, jnp.ones((16, 64)), w)
    c2 = obs.analyze(f, jnp.ones((32, 64)), w)
    assert c1.flops and c2.flops
    assert abs(c2.flops / c1.flops - 2.0) < 0.2


def test_cost_analysis_counts_scan_body_once():
    """Documented gotcha (obs/cost.py): XLA's cost analysis counts
    static HLO, so a lax.scan body is counted ONCE regardless of trip
    count — producers of scanned-program records must therefore report
    counting='static-body' with steps_per_dispatch=1."""
    w = jnp.ones((32, 32), jnp.float32)

    def scan_n(n):
        f = jax.jit(lambda x: jax.lax.scan(
            lambda c, _: (jnp.dot(c, w), None), x, None, length=n)[0])
        return obs.analyze(f, jnp.ones((32, 32))).flops

    f1, f10 = scan_n(1), scan_n(10)
    assert f1 and f10
    assert f10 / f1 < 2.0, (f1, f10)  # NOT ~10x: body counted once


def test_collective_counts_on_2_device_psum_step(eight_devices):
    """A shard_map psum step on a 2-device CPU mesh: the jaxpr walk sees
    the explicit psum, the compiled HLO carries an all-reduce."""
    mesh = make_mesh({"data": 2}, devices=eight_devices[:2])

    def step(x):
        return lax.pmean(jnp.sum(x * x), "data")

    body = jax.shard_map(step, mesh=mesh, in_specs=(P("data"),),
                         out_specs=P(), check_vma=False)
    x = jnp.arange(8, dtype=jnp.float32)

    jx = obs.jaxpr_collective_counts(body, x)
    assert jx.get("psum", 0) >= 1, jx

    costs = obs.analyze(jax.jit(body), x)
    assert costs.collectives.get("all-reduce", 0) >= 1, costs.collectives


def test_hlo_collective_counts_dedups_async_pairs():
    txt = """
      %ar = f32[4] all-reduce-start(f32[4] %x), replica_groups={}
      %ad = f32[4] all-reduce-done(f32[4] %ar)
      %ag = f32[8] all-gather(f32[4] %y), dimensions={0}
    """
    counts = obs.hlo_collective_counts(txt)
    assert counts == {"all-reduce": 1, "all-gather": 1}


# -------------------------------------------------------------- schema


def test_jsonl_schema_roundtrip(tmp_path):
    """write -> parse -> validate: the required keys survive, comment
    lines skip, and a bad record is rejected loudly."""
    path = tmp_path / "run.jsonl"
    with MetricsLogger(path, echo=False) as metrics:
        metrics.log("train", step=1, loss=1.25)
        metrics.log("step_phases", steps=4,
                    phases_ms={"dispatch": 1.0, "device": 0.5})
        metrics.log("program", label="step", flops=100.0,
                    collectives={"all-reduce": 1})
    with path.open("a") as fh:
        fh.write("# capture marker comment\n")

    records = obs.load_records(path, strict=True)
    assert [r["event"] for r in records] == ["train", "step_phases", "program"]
    for r in records:
        assert r["schema"] == obs.SCHEMA_VERSION
        assert obs.validate_record(r) is r

    with pytest.raises(ValueError, match="missing required keys"):
        obs.validate_record({"event": "train"})
    with pytest.raises(ValueError, match="missing keys"):
        obs.validate_record(obs.make_record("program", 0.0, label="x"))
    with pytest.raises(ValueError, match="must be an int"):
        obs.validate_record({"schema": "2", "event": "x", "t": 0.0})

    # Each logger open appends a '# run ...' boundary marker, so two runs
    # into one file stay separable: iter_runs splits on the markers and
    # the report renders per-run tables instead of blending runs.
    with MetricsLogger(path, echo=False) as metrics:
        metrics.log("train", step=2, loss=0.5)
    markers = [ln for ln in path.read_text().splitlines()
               if ln.startswith("# run ")]
    assert len(markers) == 2
    assert len(obs.load_records(path, strict=True)) == 4
    runs = list(obs.iter_runs(path, strict=True))
    assert [len(r) for r in runs] == [3, 1]

    # dump_records is the write-path twin: a dumped file reads back
    # identically (one run — no markers).
    copy = tmp_path / "copy.jsonl"
    obs.dump_records(obs.load_records(path, strict=True), copy)
    assert obs.load_records(copy, strict=True) == obs.load_records(
        path, strict=True
    )
    assert [len(r) for r in obs.iter_runs(copy)] == [4]


def test_metrics_logger_closes_on_exception(tmp_path):
    """The context manager must not leak the JSONL handle when the body
    raises — the records written before the failure stay readable."""
    path = tmp_path / "crash.jsonl"
    with pytest.raises(RuntimeError, match="boom"):
        with MetricsLogger(path, echo=False) as metrics:
            metrics.log("train", step=1, loss=0.5)
            raise RuntimeError("boom")
    assert not metrics.jsonl_enabled  # handle closed
    assert [r["event"] for r in obs.load_records(path)] == ["train"]


# -------------------------------------------------------------- device


def test_memory_stats_smoke():
    """Every backend: the snapshot has one entry per device and never
    raises. Backends without allocator stats (CPU) skip the value
    checks cleanly."""
    snap = obs.memory_snapshot()
    assert len(snap) == len(jax.devices())
    assert all({"id", "platform", "stats"} <= e.keys() for e in snap)
    if all(e["stats"] is None for e in snap):
        assert obs.hbm_peak_bytes() is None
        pytest.skip("backend exposes no memory_stats")
    peak = obs.hbm_peak_bytes()
    assert isinstance(peak, int) and peak > 0


# ------------------------------------------------------------- timers


def test_step_timer_guards_and_phases():
    t = StepTimer()
    with pytest.raises(RuntimeError, match="before start"):
        t.stop()
    t.start()
    with t.phase("data"):
        pass
    with t.phase("dispatch"):
        pass
    assert t.stop(2) >= 0.0
    with pytest.raises(RuntimeError):  # double stop
        t.stop()
    ms = t.phases_ms()
    assert set(ms) >= {"data", "dispatch"}
    t.reset()
    assert t.steps == 0 and t.total_s == 0.0 and t.phases_ms() == {}


def test_span_nesting_emits_joined_names(tmp_path):
    with MetricsLogger(tmp_path / "spans.jsonl", echo=False) as metrics:
        with obs.span("epoch", metrics=metrics):
            assert obs.current_path() == "epoch"
            with obs.span("eval", metrics=metrics):
                assert obs.current_path() == "epoch/eval"
        assert obs.current_path() == ""
    names = [r["name"] for r in obs.load_records(tmp_path / "spans.jsonl")]
    assert names == ["epoch/eval", "epoch"]  # inner closes first
    assert all(r["ms"] >= 0 for r in obs.load_records(tmp_path / "spans.jsonl"))


# ------------------------------------------------------------- report


def _telemetry_run(tmp_path):
    """A tiny REAL training run with the JSONL sink — the acceptance
    path: per-step records with phase timings, cost-analysis FLOPs, and
    collective counts, all in one file."""
    from mpi_cuda_cnn_tpu.data.datasets import synthetic_stripes
    from mpi_cuda_cnn_tpu.models.presets import get_model
    from mpi_cuda_cnn_tpu.train.trainer import Trainer
    from mpi_cuda_cnn_tpu.utils.config import Config

    path = tmp_path / "run.jsonl"
    ds = synthetic_stripes(num_train=128, num_test=32)
    cfg = Config(model="reference_cnn", epochs=1, batch_size=32,
                 log_every=2, eval_every=1, num_devices=1)
    with MetricsLogger(path, echo=False) as metrics:
        Trainer(get_model("reference_cnn"), ds, cfg, metrics=metrics).train()
    return path


def test_trainer_telemetry_and_report(tmp_path):
    path = _telemetry_run(tmp_path)
    records = obs.load_records(path, strict=True)
    by_event = {}
    for r in records:
        by_event.setdefault(r["event"], []).append(r)

    assert "train" in by_event
    prog = by_event["program"][0]
    assert prog["flops"] and prog["flops"] > 0
    assert isinstance(prog["collectives"], dict)
    phases = by_event["step_phases"][0]
    assert phases["steps"] > 0 and "dispatch" in phases["phases_ms"]
    assert by_event["memory"][0]["devices"]

    summary = obs.summarize(records)
    md = obs.render_markdown(summary)
    assert "step phases" in md and "program" in md and "flops" in md
    # The CLI form returns success and prints the same tables.
    from mpi_cuda_cnn_tpu.obs.report import report_main

    assert report_main([str(path)]) == 0


def test_cli_report_subcommand(tmp_path, capsys):
    path = tmp_path / "r.jsonl"
    with MetricsLogger(path, echo=False) as metrics:
        metrics.log("train", step=1, loss=2.0)
        metrics.log("train", step=2, loss=1.0)
    from mpi_cuda_cnn_tpu.cli import main

    assert main(["report", str(path), "--format", "json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["train"]["last_loss"] == 1.0
    assert main(["report", str(tmp_path / "missing.jsonl")]) == 1


def test_report_reads_pre_schema_capture_files(tmp_path):
    """Pre-schema capture files (comments + schemaless rows) must
    keep parsing — the reader skips what it cannot validate."""
    path = tmp_path / "cap.jsonl"
    path.write_text(
        "# capture 2026-07-31T17:00:00Z\n"
        '{"capture_step": "probe", "rc": 0}\n'
        '{"bench": "lm", "tokens_per_s": 123}\n'
    )
    records = obs.load_records(path)
    assert len(records) == 2
    summary = obs.summarize(records)
    assert summary["events"] == {}
