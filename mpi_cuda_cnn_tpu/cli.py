"""Python CLI — the framework's main entry point.

Keeps the reference's CLI contract (4 positional IDX paths, cnn.c:408-411;
exit 100 on bad argc, exit 111 on unreadable files) while exposing every
compiled-in constant of the reference as a flag (utils/config.py). The C
driver (native/) offers the same surface for the north star's
`--device=tpu` C-binary form.

    python -m mpi_cuda_cnn_tpu train-images train-labels t10k-images t10k-labels
    python -m mpi_cuda_cnn_tpu --dataset synthetic --model lenet5_relu --epochs 3
    python -m mpi_cuda_cnn_tpu --metrics-jsonl run.jsonl ...   # telemetry sink
    python -m mpi_cuda_cnn_tpu report run.jsonl                # summary tables
    python -m mpi_cuda_cnn_tpu serve-bench --requests 32       # serving bench
    python -m mpi_cuda_cnn_tpu fleet-bench --replicas 4        # fleet storm
    python -m mpi_cuda_cnn_tpu trace run.jsonl --request 3     # lifecycle trace
    python -m mpi_cuda_cnn_tpu explain run.jsonl --worst ttft  # causal blame
    python -m mpi_cuda_cnn_tpu top run.jsonl                   # live dashboard
    python -m mpi_cuda_cnn_tpu compare base.jsonl new.jsonl    # regression gate
    python -m mpi_cuda_cnn_tpu health run.jsonl --slo slo.json # SLO verdicts
    python -m mpi_cuda_cnn_tpu lint --format json              # invariant lint
    python -m mpi_cuda_cnn_tpu replay run.jsonl --at-tick 40   # state replay
    python -m mpi_cuda_cnn_tpu diverge a.jsonl b.jsonl         # 1st divergence
    python -m mpi_cuda_cnn_tpu chaos --episodes 50             # fault search
"""

from __future__ import annotations

import dataclasses
import sys

from .data.datasets import get_dataset, load_idx_dataset
from .data.idx import IdxError
from .faults import FaultInjector, Preempted, PreemptionGuard, supervise
from .models.presets import get_model
from .obs.metrics import MetricsRegistry
from .parallel.distributed import initialize_distributed
from .train.trainer import Trainer
from .utils.backend import DeviceError, claim_device, device_stamp
from .utils.config import Config, parse_args
from .utils.logging import MetricsLogger, get_logger


def _select_device(cfg, log) -> bool:
    """Honor --device through the one helper (utils/backend), which
    also places the compile cache and logs what JAX found as the first
    log line; False (exit 2) when the requested device is not there.
    The mesh follows with the first JSONL record, once a trainer has
    built it."""
    try:
        claim_device(cfg.device, log.info)
    except DeviceError as e:
        log.error("%s", e)
        return False
    return True


def _fault_setup(cfg, log):
    """Validate the supervisor/fault flags up front. Returns
    (rc, injector): rc != 0 is a config error (nothing was run);
    injector is the ONE FaultInjector for the whole supervised run —
    faults fired in a crashed attempt stay fired, so a restart proves
    recovery instead of re-tripping the same crash."""
    if cfg.max_restarts > 0 and not cfg.checkpoint_dir:
        log.error("--max-restarts needs --checkpoint-dir: a restarted "
                  "attempt resumes from the latest valid checkpoint")
        return 2, None
    try:
        return 0, FaultInjector(cfg.fault_plan) if cfg.fault_plan else None
    except ValueError as e:
        log.error("bad --fault-plan: %s", e)
        return 2, None


def _supervised(cfg, log, metrics, first_trainer, make_trainer,
                registry=None):
    """Run training under the crash-safe supervisor.

    `first_trainer` was built by the caller OUTSIDE this call (so a
    construction/config error surfaces once, with the caller's own
    error handling, and is never mistaken for a mid-training crash);
    each restarted attempt rebuilds with resume forced — the
    supervisor's whole contract is continue-from-checkpoint. `registry`
    is the run-wide obs.MetricsRegistry the trainers share (restart and
    step totals survive the rebuilds). Returns (result, last_trainer);
    training exceptions propagate once restarts are exhausted."""
    trainer = first_trainer

    def attempt(n: int):
        nonlocal trainer
        if n > 0:
            trainer = make_trainer(dataclasses.replace(cfg, resume=True))
        return trainer.train()

    result = supervise(attempt, max_restarts=cfg.max_restarts,
                       logger=log, metrics=metrics, registry=registry)
    return result, trainer


def run(cfg: Config) -> int:
    log = get_logger()
    if not _select_device(cfg, log):
        return 2
    initialize_distributed()

    try:
        if cfg.dataset == "idx":
            ds = load_idx_dataset(
                "idx",
                cfg.train_images,
                cfg.train_labels,
                cfg.test_images,
                cfg.test_labels,
            )
        else:
            ds = get_dataset(cfg.dataset, data_dir=cfg.data_dir)
    except (OSError, IdxError, TypeError) as e:
        # The reference exits 111 on any file problem (cnn.c:432,440).
        log.error("data load failed: %s", e)
        return 111
    except (KeyError, ValueError) as e:
        log.error("bad dataset config: %s", e)
        return 2

    try:
        model = get_model(cfg.model, input_shape=ds.input_shape)
    except KeyError as e:
        log.error("%s", e)
        return 2
    log.info("model=%s dataset=%s input=%s", model.name, ds.name, ds.input_shape)
    rc, faults = _fault_setup(cfg, log)
    if rc:
        return rc
    # The context manager closes the JSONL sink even when the trainer
    # raises mid-run — the records written so far must survive.
    # The preemption guard hooks SIGTERM/SIGINT for the whole run
    # (ISSUE 5): a scheduler's eviction notice finishes the in-flight
    # step, snapshots, and exits EXIT_PREEMPTED instead of dying
    # mid-write; uninstalled on the way out so embedding callers (tests,
    # the C ABI) never inherit our handlers.
    with MetricsLogger(path=cfg.metrics_jsonl) as metrics, \
            PreemptionGuard() as guard:
        # ONE runtime registry for the whole (possibly supervised) run:
        # restart/step totals must survive per-attempt trainer rebuilds.
        registry = MetricsRegistry()

        def make_trainer(c):
            return Trainer(model, ds, c, metrics=metrics, faults=faults,
                           preempt=guard, registry=registry)

        # First construction outside the retry loop AND outside
        # _supervised: a config error (bad nan-policy, indivisible
        # batch, ...) can never succeed on retry — it fails once, fast
        # — while mid-training errors propagate with their tracebacks.
        try:
            first = make_trainer(cfg)
        except ValueError as e:
            log.error("trainer setup failed: %s", e)
            return 2
        metrics.log("device", **device_stamp(first.mesh))
        try:
            result, _ = _supervised(cfg, log, metrics, first, make_trainer,
                                    registry=registry)
        except Preempted as e:
            if e.resumable:
                log.warning("run preempted (%s); exiting %d — relaunch "
                            "with --resume to continue", e, e.code)
            else:
                log.warning("run preempted (%s) with no checkpoint to "
                            "resume from; exiting %d", e, e.code)
            return int(e.code)
    log.info(
        "done: epochs=%d acc=%.4f mean_step=%.3fms",
        result.epochs_run,
        result.test_accuracy,
        result.mean_step_ms,
    )
    return 0


def run_lm(argv: list[str]) -> int:
    """The `lm` subcommand: train the transformer LM (long-context
    path — flash attention, data/seq meshes, MoE)."""
    from .train.lm_trainer import LMTrainer
    from .utils.config import parse_lm_args

    cfg = parse_lm_args(argv)
    log = get_logger()
    if not _select_device(cfg, log):
        return 2
    rc, faults = _fault_setup(cfg, log)
    if rc:
        return rc
    initialize_distributed()
    with MetricsLogger(path=cfg.metrics_jsonl) as metrics, \
            PreemptionGuard() as guard:
        registry = MetricsRegistry()  # shared across supervised attempts

        def make_trainer(c):
            return LMTrainer(c, metrics=metrics, faults=faults,
                             preempt=guard, registry=registry)

        # First construction outside _supervised: setup errors map to
        # rc=2 exactly once; mid-training errors keep their tracebacks.
        try:
            first = make_trainer(cfg)
        except (OSError, ValueError) as e:
            log.error("lm setup failed: %s", e)
            return 2
        # What ran includes what "auto" resolved to: the fused kernel
        # or the XLA oracle (train/lm.pick_attn_impl).
        metrics.log("device", **device_stamp(first.mesh),
                    attn=first.attn_impl)
        log.info(
            "lm model=d%dx%d h%d seq=%d vocab=%d moe=%d mesh=%s attn=%s",
            cfg.dim, cfg.depth, cfg.heads, cfg.seq_len, first.model.vocab,
            cfg.moe_experts, dict(first.mesh.shape), first.attn_impl,
        )
        try:
            result, trainer = _supervised(cfg, log, metrics, first,
                                          make_trainer, registry=registry)
        except Preempted as e:
            if e.resumable:
                log.warning("run preempted (%s); exiting %d — relaunch "
                            "with --resume to continue", e, e.code)
            else:
                log.warning("run preempted (%s) with no checkpoint to "
                            "resume from; exiting %d", e, e.code)
            return int(e.code)
        log.info(
            "done: steps=%d eval_ppl=%.3f tokens/s=%.0f",
            result.steps_run, result.eval_ppl, result.tokens_per_s,
        )
        if cfg.sample_tokens:
            _, cont = trainer.sample(
                cfg.sample_tokens, temperature=cfg.sample_temperature,
                seed=cfg.seed,
            )
            # Char-level corpora (self / file / synthetic-mod-251) decode as
            # bytes; anything out of byte range prints as escapes.
            text = bytes(int(t) & 0xFF for t in cont)
            log.info("sample (%d tokens): %r", cfg.sample_tokens, text)
    return 0


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] == "train":
        # Explicit alias for the default command, so the supervisor form
        # reads naturally: `mctpu train --max-restarts 3 ...`.
        argv = argv[1:]
    if argv and argv[0] == "lm":
        return run_lm(argv[1:])
    if argv and argv[0] == "report":
        # Offline: summarize a metrics JSONL run (obs.report) — no jax
        # device init, safe on any machine.
        from .obs.report import report_main

        return report_main(argv[1:])
    if argv and argv[0] == "trace":
        # Offline: reconstruct per-request lifecycles from a serving
        # run's tick records (obs.timeline) — jax-free.
        from .obs.timeline import trace_main

        return trace_main(argv[1:])
    if argv and argv[0] == "explain":
        # Offline: causal critical-path attribution — per-request blame
        # trees that sum exactly to end-to-end latency, aggregate blame
        # and top-blocker tables (obs.causal, ISSUE 11) — jax-free.
        from .obs.causal import explain_main

        return explain_main(argv[1:])
    if argv and argv[0] == "replay":
        # Offline: deterministic flight-recorder replay — reconstruct
        # the full serving state from a run's tick trail, cross-checking
        # the stamped per-tick state digests (obs.replay, ISSUE 15) —
        # jax-free.
        from .obs.replay import replay_main

        return replay_main(argv[1:])
    if argv and argv[0] == "diverge":
        # Offline: first-divergence localization between two
        # identical-seed trails — the determinism gates' forensic tool
        # (obs.diverge, ISSUE 15) — jax-free.
        from .obs.diverge import diverge_main

        return diverge_main(argv[1:])
    if argv and argv[0] == "top":
        # Live dashboard: tail (or replay) a metrics JSONL and render
        # the engine/trainer gauges in place (obs.top) — jax-free.
        from .obs.top import top_main

        return top_main(argv[1:])
    if argv and argv[0] == "compare":
        # Perf-regression gate: compare run files / bench captures on
        # named metrics, exit 1 on regression (obs.regress) — jax-free.
        from .obs.regress import compare_main

        return compare_main(argv[1:])
    if argv and argv[0] == "autosize":
        # Offline capacity search: sweep candidate fleet topologies at
        # a fixed chip budget as seeded SimCompute storms, score by
        # SLO-attained goodput, emit a deterministic goodput frontier +
        # recommendation; --seed-from prunes the sweep from a finished
        # run's blame profile (obs.autosize, ISSUE 16) — jax-free.
        from .obs.autosize import autosize_main

        return autosize_main(argv[1:])
    if argv and argv[0] == "chaos":
        # Seeded fault-schedule search: sample multi-fault plans from
        # the live faults.SITES registry, run each through the fleet
        # storm under a global invariant oracle (terminal-exactly-once,
        # closed-form outputs, blame conservation, pool/tier clean
        # exit, zero-drift replay, bitwise re-run), ddmin-shrink any
        # violation to a one-line --fault-plan repro (chaos/, ISSUE 19)
        # — jax-free.
        from .chaos.cli import chaos_main

        return chaos_main(argv[1:])
    if argv and argv[0] == "health":
        # SLO health gate: per-tenant verdict table + alert replay for
        # a finished run, exit 1 on violation (obs.health, ISSUE 8) —
        # jax-free.
        from .obs.health import health_main

        return health_main(argv[1:])
    if argv and argv[0] == "lint":
        # Static analyzer: the framework-invariant rules MCT001-MCT007
        # over the repo's own contracts (analysis/, ISSUE 10) —
        # jax-free, gates CI on exit code.
        from .analysis.cli import lint_main

        return lint_main(argv[1:])
    if argv and argv[0] == "serve-bench":
        # Serving bench: paged-KV continuous batching vs static
        # batching under Poisson arrivals (serve/bench.py).
        from .serve.bench import serve_bench_main

        return serve_bench_main(argv[1:])
    if argv and argv[0] == "fleet-bench":
        # Fleet bench: N replicas behind the failure-aware router under
        # a seeded Poisson storm with injected replica crashes/joins —
        # deterministic under FakeClock (serve/fleet.py, ISSUE 7).
        from .serve.bench import fleet_bench_main

        return fleet_bench_main(argv[1:])
    cfg = parse_args(argv)
    return run(cfg)


if __name__ == "__main__":
    sys.exit(main())
