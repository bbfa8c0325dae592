"""Train/eval loops.

The reference's training loop (cnn.c:445-474): per-sample forward/backward
with gradients accumulated over 32 samples, update every 32nd step at
lr/32, running squared-error print every 1000 samples; eval is a forward
argmax sweep printing "ntests=%d, ncorrect=%d" (cnn.c:494-518). Here the
loop is batched (batch == the reference's accumulator period — identical
averaged gradient, SURVEY.md §7 hard-part (a)), the step is one jitted SPMD
program over the device mesh, and the host loop only feeds batches and
reads metrics.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..data.pipeline import normalize_images, one_hot
from ..models.initializers import get_initializer
from ..ops import softmax_cross_entropy, squared_error_total, stable_softmax
from ..parallel.dp import (
    dp_shard_batch,
    dp_shard_perm,
    make_dp_eval_step,
    make_dp_scan_epoch,
    make_dp_train_step,
    replicate,
)
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, PIPE_AXIS, make_mesh
from ..parallel.pp import (
    make_pipeline_plan,
    make_pp_forward,
    make_pp_scan_epoch,
    make_pp_state,
    make_pp_train_step,
    microbatch,
    pp_shard_batch,
)
from ..parallel.tp import (
    make_tp_eval_step,
    make_tp_scan_epoch,
    make_tp_state,
    make_tp_train_step,
)
from ..obs import cost as obs_cost
from ..obs.device import emit_step_telemetry
from ..obs.trace import span
from ..faults import (
    MAX_NAN_ROLLBACKS,
    NanGuard,
    NonFiniteLossError,
    PreemptionGuard,
    RollbackToCheckpoint,
    all_finite,
    drain_preemption,
    poison_batch,
    step_is_finite,
)
from ..obs.metrics import MetricsRegistry
from ..parallel.distributed import barrier, process_info
from ..utils.logging import MetricsLogger, get_logger
from ..utils.profiling import StepTimer, profile_trace
from .checkpoint import (
    AsyncCheckpointer,
    restore_latest,
    validate_resume_meta,
)
from .optimizer import make_optimizer


def make_loss_fn(model, *, backend: str = "xla", compute_dtype=None,
                 remat: bool = False):
    """Softmax-CE loss + the reference's metrics (squared-error total,
    cnn.c:275-282; argmax accuracy, cnn.c:508-513)."""

    def loss_fn(params, x, y_onehot):
        logits = model.apply(params, x, backend=backend,
                             compute_dtype=compute_dtype, remat=remat)
        loss = softmax_cross_entropy(logits, y_onehot)
        probs = stable_softmax(logits)
        acc = jnp.mean(
            (jnp.argmax(logits, -1) == jnp.argmax(y_onehot, -1)).astype(jnp.float32)
        )
        return loss, {"etotal": squared_error_total(probs, y_onehot), "acc": acc}

    return loss_fn


@dataclasses.dataclass
class TrainResult:
    epochs_run: int
    final_step: int
    test_accuracy: float
    ntests: int
    ncorrect: int
    epoch_seconds: list[float]
    mean_step_ms: float


class Trainer:
    """End-to-end trainer: model + dataset + mesh -> trained params.

    Single-device and multi-device use the same code path: a 1-device mesh
    makes the DP collectives identity ops, so the SPMD program is the only
    train step there is.
    """

    def __init__(self, model, dataset, config, *, mesh=None,
                 metrics: MetricsLogger | None = None, faults=None,
                 preempt: PreemptionGuard | None = None, registry=None,
                 clock=None):
        self.model = model
        self.ds = dataset
        self.cfg = config
        self.log = get_logger()
        self.metrics = metrics or MetricsLogger()
        # Runtime metrics registry (ISSUE 6): step-time histogram,
        # samples/s gauge, liveness counters. The CLI passes ONE shared
        # registry so totals (steps, restarts) survive supervisor
        # rebuilds; standalone construction gets a private one.
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        # `clock` has the time.perf_counter call shape and is the time
        # source for epoch wall-clocks and the step timers feeding the
        # registry fold — a FakeClock makes telemetry deterministic (the
        # PR-4 contract).
        self._clock = clock if clock is not None else time.perf_counter
        # Fault hooks + the NaN/Inf guard (ISSUE 4). `faults` is a
        # faults.FaultInjector; the CLI builds one from --fault-plan and
        # shares it across supervisor restarts (fired faults stay fired).
        # The guard's policy rules live in faults.NanGuard — ONE
        # implementation for this trainer and the LM's.
        self.faults = faults
        # Preemption guard (ISSUE 5): the CLI installs one on
        # SIGTERM/SIGINT and shares it; an un-installed default still
        # answers injected `preempt@train.step` faults, so elasticity
        # tests never touch real signals.
        self._preempt = preempt if preempt is not None else PreemptionGuard()
        self._nan = NanGuard(getattr(config, "nan_policy", "off"),
                             getattr(config, "nan_max_bad", 3))
        self._finite_fn = jax.jit(all_finite) if self._nan.active else None

        ndev = config.num_devices or len(jax.devices())
        if mesh is None:
            from ..utils.config import parse_mesh_shape

            axes = parse_mesh_shape(config.mesh_shape, ndev)
            mesh = make_mesh(axes, devices=jax.devices()[:ndev])
        self.mesh = mesh
        n_data = self.mesh.shape.get(DATA_AXIS, 1)
        if config.batch_size % n_data:
            raise ValueError(
                f"batch_size {config.batch_size} not divisible by data-axis size {n_data}"
            )
        if config.grad_accum > 1 and (config.batch_size // n_data) % config.grad_accum:
            raise ValueError(
                f"per-device batch {config.batch_size // n_data} not divisible "
                f"by grad_accum {config.grad_accum}"
            )
        if config.elastic_width:
            # Width-invariant reduction rides the plain shard_map DP
            # step only: sharded-param layouts (TP/FSDP/PP) change WHAT
            # is reduced with the width, not just how — cross-width
            # bitwise resume is out of reach there by construction.
            from ..parallel.elastic import check_elastic_width

            if (self.mesh.shape.get(MODEL_AXIS, 1) > 1
                    or self.mesh.shape.get(PIPE_AXIS, 1) > 1
                    or config.fsdp):
                raise ValueError(
                    "--elastic-width needs a pure data-parallel mesh "
                    f"(mesh_shape={config.mesh_shape!r}/--fsdp shard "
                    "params; cross-width bitwise resume is only defined "
                    "for replicated state)"
                )
            if config.grad_accum > 1:
                raise ValueError(
                    "--elastic-width already scans canonical "
                    "microbatches; --grad-accum is redundant with it — "
                    "drop one of the two"
                )
            check_elastic_width(config.elastic_width, config.batch_size,
                                n_data)

        compute_dtype = (
            jnp.bfloat16 if config.compute_dtype == "bfloat16" else None
        )
        backend = "pallas" if config.use_pallas else "xla"
        self.loss_fn = make_loss_fn(model, backend=backend,
                                    compute_dtype=compute_dtype,
                                    remat=config.remat)

        from ..data.augment import make_augment

        self._augment = make_augment(config.augment, pad=config.aug_pad)
        # fold_in needs a distinct stream from param init; offset the seed.
        self._aug_seed = config.seed + 0x5EED

        # Normalized host copies are built lazily (train_x/train_y
        # properties): the default scanned path stages raw uint8 on device
        # and never needs the float32 host materialization.
        self._train_x = None
        self._train_y = None
        self.num_train = len(dataset.train_images)
        self.test_x = normalize_images(dataset.test_images)
        self.test_labels = np.asarray(dataset.test_labels)

        self.steps_per_epoch = self.num_train // config.batch_size
        total_steps = self.steps_per_epoch * config.epochs
        # The pipelined step clips IN-STEP with a cross-rank-correct
        # global norm (its packed rows are sharded, so optax's
        # clip_by_global_norm would compute a per-rank partial norm) —
        # same split as the LM trainer's sharded-param paths.
        pp_clip = self.mesh.shape.get(PIPE_AXIS, 1) > 1
        self.optimizer = make_optimizer(
            config.lr,
            momentum=config.momentum,
            schedule=config.lr_schedule,
            total_steps=total_steps or None,
            grad_clip=0.0 if pp_clip else config.grad_clip,
        )

        # One keyed init, replicated to every device (fixes the reference's
        # divergent never-synchronized per-rank init, SURVEY.md 2.6c).
        init = get_initializer(config.init)
        param_dtype = jnp.dtype(config.param_dtype)
        params = model.init(jax.random.key(config.seed), init, dtype=param_dtype)
        predict = lambda params, x: model.apply(
            params, x, backend=backend, compute_dtype=compute_dtype
        )
        self.n_model = self.mesh.shape.get(MODEL_AXIS, 1)
        self.n_pipe = self.mesh.shape.get(PIPE_AXIS, 1)
        self._pp_M = 1  # microbatches per step; >1 only on the PP path
        if self.n_pipe == 1 and config.num_microbatches:
            raise ValueError(
                "--num-microbatches requires a 'pipe' mesh axis "
                f"(mesh_shape={config.mesh_shape!r} has none)"
            )
        if self.n_pipe > 1:
            # Pipeline(+data) parallel: stage-sharded params, GPipe
            # microbatch schedule (parallel/pp.py). Beyond the reference,
            # which runs layers sequentially in one process (cnn.c:255-267).
            # Composes with --augment (applied in the step body, keyed like
            # the DP path), --remat (jax.checkpoint per stage), --fsdp
            # (ZeRO sharding of the packed stage rows over 'data'), and TP.
            if config.grad_accum > 1:
                raise ValueError(
                    "--grad-accum is redundant on the pipeline path: "
                    "--num-microbatches already accumulates over "
                    "micro-batches"
                )
            if param_dtype != jnp.float32:
                raise ValueError(
                    "pipeline parallelism keeps master params in the packed "
                    "f32 stage buffers; use --compute-dtype for low-precision "
                    f"compute (got param_dtype={config.param_dtype})"
                )
            if config.fsdp and n_data <= 1:
                raise ValueError(
                    "FSDP x PP shards the packed stage rows over 'data'; "
                    f"add a data axis of size > 1 (mesh_shape="
                    f"{config.mesh_shape!r})"
                )
            self._pp_M = config.num_microbatches or self.n_pipe
            if config.batch_size % (self._pp_M * n_data):
                raise ValueError(
                    f"batch_size {config.batch_size} not divisible by "
                    f"num_microbatches x data-axis ({self._pp_M} x {n_data})"
                )
            self._pp_plan = make_pipeline_plan(
                model, self.n_pipe, backend=backend,
                compute_dtype=compute_dtype, n_model=self.n_model,
                remat=config.remat,
                fsdp_degree=n_data if config.fsdp else 1,
            )
            self.state = make_pp_state(
                self._pp_plan, params, self.optimizer, self.mesh
            )
            self.train_step = make_pp_train_step(
                self._pp_plan, self.optimizer, self.mesh, self.state,
                donate=config.donate,
                augment=self._augment, aug_seed=self._aug_seed,
                grad_clip=config.grad_clip,
            )
            self.eval_step = make_pp_forward(self._pp_plan, self.mesh)
        elif self.n_model > 1 or config.fsdp:
            # GSPMD paths — sharding lives in the STATE PLACEMENT, the
            # step is the plain jitted one and XLA inserts the
            # collectives: TP shards params over 'model' (parallel/tp.py;
            # the reference has no TP at all, SURVEY.md §2 checklist),
            # FSDP shards params + optimizer state ZeRO-style over the
            # same 'data' axis as the batch (parallel/fsdp.py).
            if config.fsdp:
                from ..parallel.fsdp import make_fsdp_state

                base = None
                if self.n_model > 1:
                    # FSDP x TP: features over 'model' (Megatron), the
                    # largest remaining dim over 'data' (ZeRO).
                    from ..parallel.tp import tp_param_specs

                    base = tp_param_specs(model, self.mesh)
                self.state = make_fsdp_state(
                    params, self.optimizer, self.mesh, base_specs=base
                )
            else:
                self.state = make_tp_state(
                    model, params, self.optimizer, self.mesh
                )
            self.train_step = make_tp_train_step(
                self.loss_fn, self.optimizer, donate=config.donate,
                augment=self._augment, aug_seed=self._aug_seed,
                grad_accum=config.grad_accum,
            )
            self.eval_step = make_tp_eval_step(predict)
        else:
            opt_state = self.optimizer.init(params)
            self.state = replicate(
                {"params": params, "opt_state": opt_state,
                 "step": jnp.zeros((), jnp.int32)},
                self.mesh,
            )
            self.train_step = make_dp_train_step(
                self.loss_fn, self.optimizer, self.mesh, donate=config.donate,
                augment=self._augment, aug_seed=self._aug_seed,
                grad_accum=config.grad_accum,
                elastic_width=config.elastic_width,
            )
            self.eval_step = make_dp_eval_step(predict, self.mesh)
        # Scanned-epoch path: built lazily on first use (run_epoch), since
        # it stages the uint8 training set into device memory.
        self._scan_epoch_fn = None
        self._dev_images = None
        self._dev_labels = None
        self._eval_batch = self._pick_eval_batch(
            len(self.test_x), n_data * self._pp_M
        )
        # Shuffle order is a pure function of (seed, epoch) — see
        # _epoch_order — so every entry point (train(), run_epoch() via
        # the C ABI, a resumed process after preemption) reconstructs the
        # exact batch order without any serialized RNG state. This is what
        # makes STEP-granular resume bitwise-exact (SURVEY.md §5.3/5.4
        # "elastic recovery"): epoch = step // steps_per_epoch, position
        # = step % steps_per_epoch, order = _epoch_order(epoch).

        if self.steps_per_epoch == 0:
            raise ValueError(
                f"batch_size {config.batch_size} exceeds train set size "
                f"{self.num_train}: no full batches"
            )

        # Telemetry: compiled-program accounting is emitted once per
        # program label (obs.cost — an extra AOT compile, so only when a
        # JSONL sink wants it); per-epoch phase/memory records ride the
        # same gate.
        self._programs_logged: set[str] = set()

        # One checkpointer for every save site; async by default (the
        # step loop pays only the host snapshot, the npz write overlaps
        # the next steps; train() drains it before returning). Each
        # checkpoint's manifest entry records the topology it was
        # written under (mesh axes + elastic width — what a
        # topology-changed resume validates against), and on multihost
        # runs process 0 is the only writer with a barrier fencing the
        # publication (train/checkpoint.py).
        from ..parallel.mesh import describe_mesh

        self._proc = process_info()
        self._ckpt_meta = {
            "mesh": describe_mesh(self.mesh),
            "elastic_width": config.elastic_width,
            "process_count": self._proc.process_count,
        }
        self._ckpt = (
            AsyncCheckpointer(config.checkpoint_dir,
                              async_=config.async_checkpoint,
                              faults=faults, meta=self._ckpt_meta,
                              process=self._proc, barrier=barrier)
            if config.checkpoint_dir else None
        )

    def _epoch_order(self, epoch: int) -> np.ndarray:
        """The epoch's sample permutation — derived, never stored."""
        return np.random.default_rng((self.cfg.seed, epoch)).permutation(
            self.num_train
        )

    def _global_step(self) -> int:
        return int(jax.device_get(self.state["step"]))

    def _maybe_step_checkpoint(self, global_step: int) -> None:
        """Mid-epoch save when --checkpoint-every-steps divides the global
        step (called at batch/chunk boundaries; the host-side step count
        avoids a per-step device sync — saving itself syncs)."""
        cfg = self.cfg
        if not (cfg.checkpoint_dir and cfg.checkpoint_every_steps):
            return
        if global_step and global_step % cfg.checkpoint_every_steps == 0:
            self._ckpt.save(self.state, global_step)

    def _drain_fault_events(self) -> None:
        """Forward the injector's fired-fault records to the obs sink."""
        if self.faults is not None:
            for ev in self.faults.drain_events():
                self.metrics.log("fault", **ev)

    def _step_boundary(self, global_step: int) -> None:
        """The per-step fault/preemption hook shared by the loop and
        scanned paths: fire planned train.step faults (an injected
        ``preempt`` sets the same flag a real SIGTERM would), then
        drain the orderly-exit path (faults.drain_preemption — ONE
        implementation for this trainer and the LM's) if a preemption
        is pending."""
        if self.faults is not None:
            for f in self.faults.fire("train.step", global_step):
                if f.kind == "preempt":
                    self._preempt.request()
            self._drain_fault_events()
        drain_preemption(self._preempt, state=self.state,
                         global_step=global_step, ckpt=self._ckpt,
                         metrics=self.metrics, logger=self.log)

    def _drop_bad_update(self, gstep: int, snap) -> None:
        """Apply --nan-policy to a non-finite step (faults.NanGuard owns
        the rules; abort and rollback raise there). A plain skip drops
        the bad update by reinstalling the pre-step snapshot — with the
        step counter still ADVANCED past the dropped batch:
        state["step"] must stay equal to batches CONSUMED, or a later
        crash-restart / rollback would re-derive its resume position
        short by the skipped steps and replay already-applied batches
        (breaking the bitwise restart contract). An organic NaN replays
        deterministically to the same skip, so positions stay exact."""
        self._nan.bad_step(gstep, logger=self.log, metrics=self.metrics)
        snap = dict(snap)
        snap["step"] = np.asarray(snap["step"]) + 1
        self.place_state(snap)

    def _rollback_to_checkpoint(self) -> tuple[int, int]:
        """Reload the newest valid checkpoint after a nan-policy=restore
        rollback; returns the (epoch, skip_steps) to re-enter at."""
        if self._ckpt is not None:
            self._ckpt.wait()  # the in-flight write may BE the newest
        restored, path = restore_latest(
            self.cfg.checkpoint_dir or "", jax.device_get(self.state),
            logger=self.log, metrics=self.metrics,
        ) if self.cfg.checkpoint_dir else (None, None)
        if restored is None:
            raise NonFiniteLossError(
                "nan-policy=restore: no valid checkpoint to roll back to "
                "(set --checkpoint-dir and --checkpoint-every-steps)"
            )
        self.place_state(restored)
        self._nan.step_ok()
        spe = max(self.steps_per_epoch, 1)
        step0 = self._global_step()
        self.metrics.log("fault", kind="nan_restore", step=step0,
                         path=path.name)
        self.log.warning("nan-policy=restore: rolled back to %s (step %d)",
                         path, step0)
        return step0 // spe, step0 % spe

    def _maybe_log_program(self, label: str, fn, *args,
                           steps_per_dispatch: int = 1,
                           counting: str = "program") -> None:
        """Emit ONE "program" record per program label: FLOPs/bytes from
        XLA cost analysis of the step actually dispatched, collectives
        from its HLO (obs.cost). Costs an extra AOT compile, so gated on
        the JSONL sink; failures degrade to a warning."""
        if self.metrics is None or not self.metrics.jsonl_enabled:
            return
        if label in self._programs_logged:
            return
        self._programs_logged.add(label)
        if not obs_cost.log_program(
            self.metrics, label, fn, *args,
            steps_per_dispatch=steps_per_dispatch, counting=counting,
            compute_dtype=self.cfg.compute_dtype,
        ):
            self.log.warning("obs: cost analysis unavailable for %r", label)

    def _emit_epoch_obs(self, epoch: int, timer: StepTimer,
                        nsteps: int) -> None:
        """Per-epoch telemetry (the shared obs.device emit path), plus
        the runtime-registry fold (ISSUE 6): step-time histogram,
        samples/s gauge, and liveness counters — what `mctpu top`
        renders and `mctpu compare` gates. Aggregation consumes only the
        timer's already-measured intervals (no clock reads here), so a
        FakeClock-driven timer yields bitwise-identical snapshots."""
        emit_step_telemetry(self.metrics, timer, nsteps,
                            devices=list(self.mesh.devices.flat),
                            epoch=epoch)
        if nsteps <= 0:
            return
        reg = self.registry
        reg.inc("train.steps", nsteps)
        reg.inc("train.heartbeats")
        step_ms = timer.mean_step_ms
        reg.observe("train.step_ms", step_ms)
        if step_ms > 0:
            reg.set("train.samples_per_s",
                    1e3 * self.cfg.batch_size / step_ms)
        reg.emit(self.metrics, epoch=epoch)

    @staticmethod
    def _pick_eval_batch(ntest: int, granularity: int, target: int = 2048) -> int:
        """Largest eval batch <= target divisible by `granularity` (the
        data-axis size, times the microbatch count on the PP path)."""
        b = min(target, ntest)
        b -= b % granularity
        return max(b, granularity)

    def _place_batch(self, bx, by):
        """Put one host batch on the mesh in the layout the active train
        step expects: (M, mb, ...) microbatches for PP, a flat sharded
        batch otherwise."""
        bx, by = jnp.asarray(bx), jnp.asarray(by)
        if self.n_pipe > 1:
            return pp_shard_batch(microbatch(bx, by, self._pp_M), self.mesh)
        return dp_shard_batch((bx, by), self.mesh)

    @property
    def train_x(self):
        """Normalized float32 host copy, built on first use (the per-batch
        loop path); the scanned path works from the uint8 device copy."""
        if self._train_x is None:
            self._train_x = normalize_images(self.ds.train_images)
        return self._train_x

    @property
    def train_y(self):
        if self._train_y is None:
            self._train_y = one_hot(self.ds.train_labels, self.ds.num_classes)
        return self._train_y

    # ------------------------------------------------------------------

    def place_state(self, host_state) -> None:
        """Install a host-side state pytree (e.g. a restored checkpoint)
        with the SAME shardings the live state uses — replicated on the DP
        path, model-axis-sharded on the TP path. Checkpoints store full
        arrays, so restore must re-place, not just replicate."""
        shardings = jax.tree.map(lambda a: a.sharding, self.state)
        self.state = jax.device_put(host_state, shardings)

    def _dataset_bytes(self) -> int:
        """What the scanned path would stage: uint8 pixels + int32 labels."""
        return self.ds.train_images.nbytes + 4 * self.num_train

    def _oversized(self) -> bool:
        return self._dataset_bytes() > self.cfg.scan_max_bytes

    def _use_scan(self) -> bool:
        """Scanned epochs stage the WHOLE uint8 training set in HBM; for
        datasets past --scan-max-bytes that is the wrong trade — fall back
        to the streaming per-batch path (host feeds one batch per step),
        which bounds device memory at O(batch) regardless of dataset
        size. Identical math either way (test_scan_and_loop_paths_...)."""
        if not self.cfg.scan:
            return False
        if self.faults is not None and any(
            f.site == "train.batch" for f in self.faults.plan
        ):
            # A planned batch fault can only fire on the per-batch loop
            # (the scanned epoch builds batches on device); silently
            # no-op'ing the injection would let a chaos run believe it
            # exercised a fault that never happened.
            if not getattr(self, "_fault_scan_logged", False):
                self._fault_scan_logged = True
                self.log.warning(
                    "fault plan targets train.batch: per-batch stepping "
                    "(scanned epochs cannot inject batch faults)"
                )
            return False
        if self._nan.active:
            # The guard checks loss/metrics and state finiteness per
            # STEP (skip must drop exactly the bad update); the scanned
            # epoch dispatches many steps at once, so guarded runs step
            # per batch. Robustness mode trades throughput knowingly.
            if not getattr(self, "_nan_scan_logged", False):
                self._nan_scan_logged = True
                self.log.warning(
                    "--nan-policy=%s active: per-batch stepping (the "
                    "scanned epoch cannot skip/rollback single steps)",
                    self.cfg.nan_policy,
                )
            return False
        if self._oversized():
            if not getattr(self, "_scan_fallback_logged", False):
                self._scan_fallback_logged = True
                self.log.warning(
                    "dataset is %.1f GiB > --scan-max-bytes %.1f GiB: "
                    "streaming per-batch epochs instead of HBM staging",
                    self._dataset_bytes() / 2**30,
                    self.cfg.scan_max_bytes / 2**30,
                )
            return False
        return True

    def run_epoch(self, epoch: int, *, skip_steps: int = 0) -> dict:
        """Run one epoch of the jitted step over the whole training set.

        The single implementation behind both the Python CLI loop (train())
        and the C driver's ABI (runtime_abi.train_epoch) — one derived
        shuffle order (_epoch_order), one metric scheme. skip_steps > 0
        resumes MID-epoch: the first skip_steps batches of this epoch's
        order are skipped (they ran before the preemption). Metric sums
        accumulate as device scalars: no host sync per step, so dispatch
        stays async (the reference blocks on every sample by construction;
        we must not).
        """
        if self._use_scan():
            return self._run_epoch_scanned(epoch, skip_steps=skip_steps)
        cfg = self.cfg
        t0 = self._clock()
        running = None
        nsteps = 0
        order = self._epoch_order(epoch)
        b = cfg.batch_size
        timer = StepTimer(clock=self._clock)
        timer.start()
        # Oversized datasets normalize PER BATCH: the cached train_x/train_y
        # copies are a 4x float32 blow-up of the whole set — the exact host
        # materialization this path exists to avoid (see _use_scan).
        stream = self._oversized()
        labels = np.asarray(self.ds.train_labels) if stream else None
        ngood = 0  # steps whose update was kept (== nsteps unguarded)
        for start in range(skip_steps * b, self.num_train - self.num_train % b, b):
            idx = order[start : start + b]
            # 0-based global index of the step ABOUT to run; +1 below is
            # the completed-step count the checkpoint/crash hooks see.
            gstep = epoch * self.steps_per_epoch + skip_steps + nsteps
            with timer.phase("data"):
                if stream:
                    bx = normalize_images(self.ds.train_images[idx])
                    by = one_hot(labels[idx], self.ds.num_classes)
                else:
                    bx, by = self.train_x[idx], self.train_y[idx]
                if self.faults is not None:
                    for f in self.faults.fire("train.batch", gstep):
                        if f.kind == "nan":
                            bx = poison_batch(bx, f)
                            self._drain_fault_events()
                batch = self._place_batch(bx, by)
            if nsteps == 0:
                # exclude(): the analysis costs an AOT compile that must
                # not land in the step-phase attribution it feeds.
                with timer.exclude():
                    self._maybe_log_program("train_step", self.train_step,
                                            self.state, *batch)
            # skip/restore must be able to DROP the update: hold a host
            # snapshot of the pre-step state (donation consumes the
            # device buffers). Guard-only cost, documented in README.
            snap = jax.device_get(self.state) if self._nan.snapshots else None
            with timer.phase("dispatch"):
                self.state, m = self.train_step(self.state, *batch)
            nsteps += 1
            if self._nan.active and not step_is_finite(m, self._finite_fn,
                                                       self.state):
                # Drop the update (abort/rollback raise inside); the
                # checkpoint + crash hooks below still run — a skipped
                # step consumed its batch, and a planned fault at this
                # step value must not silently evaporate.
                self._drop_bad_update(gstep, snap)
            else:
                self._nan.step_ok()
                running = (m if running is None
                           else jax.tree.map(jnp.add, running, m))
                ngood += 1
                # step is the ABSOLUTE in-epoch position (skip included)
                # so a resumed run's metric stream lines up with the
                # scanned path's.
                if cfg.log_every > 0 and \
                        (skip_steps + nsteps) % cfg.log_every == 0:
                    with timer.phase("device"):
                        jax.block_until_ready(running)
                    self.metrics.log(
                        "train",
                        epoch=epoch,
                        step=skip_steps + nsteps,
                        loss=float(running["loss"]) / ngood,
                        etotal=float(running["etotal"]) / ngood,
                        acc=float(running["acc"]) / ngood,
                    )
                    # Loss as a registry gauge (ISSUE 8): health/top
                    # read it off `metrics` snapshots, with the min/max
                    # envelope the train record alone cannot carry.
                    self.registry.set("train.loss",
                                      float(running["loss"]) / ngood)
            with timer.phase("checkpoint"):
                self._maybe_step_checkpoint(gstep + 1)
            self._step_boundary(gstep + 1)
        # The epoch wall-clock must cover the COMPUTE, not the enqueue.
        with timer.phase("device"):
            jax.block_until_ready(self.state)
        # Subtract the obs AOT-compile time the timer excluded, so the
        # epoch record and step_phases record cannot disagree.
        seconds = self._clock() - t0 - timer.excluded_s
        timer.stop(max(nsteps, 1))
        self._emit_epoch_obs(epoch, timer, nsteps)
        if nsteps == 0:
            raise ValueError(
                f"no full batches: train set of {self.num_train} yields "
                f"0 batches of {cfg.batch_size}"
            )
        # Guarded epochs can drop every update (running is None): report
        # NaN metrics rather than crash — the fault events carry the why.
        return {
            "epoch": epoch,
            "steps": nsteps,
            "loss": float(running["loss"]) / ngood if ngood else float("nan"),
            "etotal": float(running["etotal"]) / ngood if ngood else float("nan"),
            "acc": float(running["acc"]) / ngood if ngood else float("nan"),
            "seconds": seconds,
        }

    def _stage_dataset(self):
        """Place the raw uint8 training set + int32 labels in device memory,
        replicated, once per run. HBM cost is the uint8 pixels (e.g. 47 MB
        for MNIST) — normalization/one-hot happen inside the scanned step."""
        from ..data.pipeline import ensure_channel_axis

        images = ensure_channel_axis(self.ds.train_images)
        self._dev_images = replicate(jnp.asarray(images, jnp.uint8), self.mesh)
        self._dev_labels = replicate(
            jnp.asarray(self.ds.train_labels, jnp.int32), self.mesh
        )
        if self.n_pipe > 1:
            self._scan_epoch_fn = make_pp_scan_epoch(
                self._pp_plan, self.optimizer, self.mesh, self.state,
                self.ds.num_classes, self._pp_M, donate=self.cfg.donate,
                augment=self._augment, aug_seed=self._aug_seed,
                grad_clip=self.cfg.grad_clip,
            )
        elif self.n_model > 1 or self.cfg.fsdp:
            # Both GSPMD paths (TP-sharded or FSDP-sharded params) scan
            # with the plain jitted epoch; shardings flow from the state.
            self._scan_epoch_fn = make_tp_scan_epoch(
                self.loss_fn, self.optimizer, self.ds.num_classes,
                donate=self.cfg.donate,
                augment=self._augment, aug_seed=self._aug_seed,
                grad_accum=self.cfg.grad_accum,
            )
        else:
            self._scan_epoch_fn = make_dp_scan_epoch(
                self.loss_fn, self.optimizer, self.mesh, self.ds.num_classes,
                donate=self.cfg.donate,
                augment=self._augment, aug_seed=self._aug_seed,
                grad_accum=self.cfg.grad_accum,
                elastic_width=self.cfg.elastic_width,
            )

    def _run_epoch_scanned(self, epoch: int, *, skip_steps: int = 0) -> dict:
        """Scanned epoch: one device dispatch per `log_every` steps (one per
        epoch when logging is off). The host sends only the int32 batch
        permutation; the dataset stays HBM-resident across epochs.
        skip_steps resumes mid-epoch; --checkpoint-every-steps additionally
        splits chunks at checkpoint boundaries so mid-epoch saves land on
        exact step counts."""
        cfg = self.cfg
        t0 = self._clock()
        timer = StepTimer(clock=self._clock)
        timer.start()
        with timer.phase("data"):
            if self._scan_epoch_fn is None:
                self._stage_dataset()
            b = cfg.batch_size
            nsteps = self.steps_per_epoch
            order = self._epoch_order(epoch)[: nsteps * b]
            perm = order.reshape(nsteps, b).astype(np.int32)

        # log_every <= 0 means logging off -> the whole epoch is one scan.
        # A shorter tail chunk costs one extra (cached thereafter) compile.
        chunk = nsteps if cfg.log_every <= 0 else min(cfg.log_every, nsteps)
        log_chunks = 0 < cfg.log_every <= nsteps  # parity with the loop path
        totals = None
        done = skip_steps
        while done < nsteps:
            end = min(done + chunk - done % chunk, nsteps)
            if cfg.checkpoint_dir and cfg.checkpoint_every_steps:
                # Break the chunk at the next global checkpoint boundary
                # (gated like _maybe_step_checkpoint — no dir, no split).
                # Chunk shapes recur once boundary offsets cycle; choosing
                # --checkpoint-every-steps to divide steps_per_epoch keeps
                # the XLA shape/compile set at its minimum.
                gstep = epoch * nsteps + done
                nxt = gstep + (
                    cfg.checkpoint_every_steps - gstep % cfg.checkpoint_every_steps
                )
                end = min(end, nxt - epoch * nsteps)
            with timer.phase("data"):
                rows = dp_shard_perm(perm[done:end], self.mesh)
            with timer.exclude():  # AOT compile out of the attribution
                # counting="static-body": XLA counts the scan body ONCE
                # (obs/cost.py docstring), so the record's flops are ~one
                # step's — steps_per_dispatch=1 keeps per-step math right.
                self._maybe_log_program(
                    "scan_epoch", self._scan_epoch_fn, self.state,
                    self._dev_images, self._dev_labels, rows,
                    steps_per_dispatch=1, counting="static-body",
                )
            with timer.phase("dispatch"):
                self.state, sums = self._scan_epoch_fn(
                    self.state, self._dev_images, self._dev_labels, rows
                )
            totals = sums if totals is None else jax.tree.map(jnp.add, totals, sums)
            done = end
            # Parity with the loop path: log only at exact multiples of
            # log_every (a short tail chunk trains but does not log).
            if log_chunks and done % cfg.log_every == 0:
                with timer.phase("device"):
                    jax.block_until_ready(totals)
                run = done - skip_steps
                self.metrics.log(
                    "train",
                    epoch=epoch,
                    step=done,
                    loss=float(totals["loss"]) / run,
                    etotal=float(totals["etotal"]) / run,
                    acc=float(totals["acc"]) / run,
                )
                # Same gauge as the loop path (ISSUE 8).
                self.registry.set("train.loss",
                                  float(totals["loss"]) / run)
            with timer.phase("checkpoint"):
                self._maybe_step_checkpoint(epoch * nsteps + done)
            # Scanned epochs advance chunk-by-chunk: crash/preempt
            # faults fire at chunk/checkpoint boundaries, where the
            # step count is exact (align `at` with a boundary) — and a
            # real SIGTERM drains here too, after the in-flight chunk.
            self._step_boundary(epoch * nsteps + done)
        with timer.phase("device"):
            jax.block_until_ready(self.state)  # see run_epoch
        seconds = self._clock() - t0 - timer.excluded_s  # see run_epoch
        run = nsteps - skip_steps
        timer.stop(max(run, 1))
        self._emit_epoch_obs(epoch, timer, run)
        return {
            "epoch": epoch,
            "steps": run,
            "loss": float(totals["loss"]) / run,
            "etotal": float(totals["etotal"]) / run,
            "acc": float(totals["acc"]) / run,
            "seconds": seconds,
        }

    def train(self) -> TrainResult:
        cfg = self.cfg
        start_epoch = 0
        skip_steps = 0  # mid-epoch resume position within start_epoch

        if cfg.resume and cfg.checkpoint_dir:
            host_state = jax.device_get(self.state)
            # restore_latest walks past corrupt checkpoints (manifest
            # crc32 verification) to the newest one that restores clean.
            restored, ckpt = restore_latest(cfg.checkpoint_dir, host_state,
                                            logger=self.log,
                                            metrics=self.metrics)
            if restored is not None:
                validate_resume_meta(ckpt, mesh=self.mesh,
                                     elastic_width=cfg.elastic_width,
                                     metrics=self.metrics, logger=self.log)
                self.place_state(restored)
                # The checkpoint this run stands on must survive every
                # later prune: a crash before the NEXT save would
                # otherwise have no valid restore point behind it.
                if self._ckpt is not None:
                    self._ckpt.protect = ckpt.name
                spe = max(self.steps_per_epoch, 1)
                step0 = self._global_step()
                start_epoch = step0 // spe
                skip_steps = step0 % spe
                self.metrics.log("ckpt", step=step0, reason="resume",
                                 path=ckpt.name)
                self.log.info(
                    "resumed from %s at epoch %d step %d (in-epoch %d)",
                    ckpt, start_epoch, step0, skip_steps,
                )

        timer = StepTimer(clock=self._clock)
        epoch_seconds: list[float] = []
        result_acc, ncorrect = 0.0, 0
        rollbacks = 0

        try:
            with profile_trace(cfg.profile_dir):
                epoch = start_epoch
                while epoch < cfg.epochs:
                    try:
                        em = self.run_epoch(epoch, skip_steps=skip_steps)
                    except RollbackToCheckpoint:
                        # --nan-policy=restore: K consecutive bad steps.
                        # Reload the newest valid checkpoint and re-enter
                        # the loop at its exact step (the derived shuffle
                        # order makes the re-run deterministic). Bounded:
                        # persistent NaNs must eventually surface.
                        rollbacks += 1
                        if rollbacks > MAX_NAN_ROLLBACKS:
                            raise NonFiniteLossError(
                                f"nan-policy=restore: rolled back "
                                f"{MAX_NAN_ROLLBACKS} times and the run "
                                "still goes non-finite"
                            ) from None
                        epoch, skip_steps = self._rollback_to_checkpoint()
                        continue
                    skip_steps = 0  # only the resumed epoch is partial
                    # Fold in the epoch's own measurement (which already
                    # excludes the obs AOT compile) instead of re-timing
                    # around it — mean_step_ms must agree with the
                    # epoch/step_phases records of the same run.
                    timer.add(em["seconds"], em["steps"])
                    epoch_seconds.append(em["seconds"])
                    self.metrics.log("epoch", epoch=epoch,
                                     seconds=em["seconds"])

                    if cfg.eval_every and (epoch + 1) % cfg.eval_every == 0:
                        with span("eval", metrics=self.metrics.sink_or_none()):
                            ntests, ncorrect = self.evaluate()
                        result_acc = ncorrect / ntests
                        self.metrics.log("eval", epoch=epoch, ntests=ntests,
                                         ncorrect=ncorrect,
                                         accuracy=result_acc)
                    if cfg.checkpoint_dir and cfg.checkpoint_every and (
                        (epoch + 1) % cfg.checkpoint_every == 0
                    ):
                        with span("checkpoint", metrics=self.metrics.sink_or_none()):
                            self._ckpt.save(self.state, self._global_step())
                    epoch += 1

            if cfg.checkpoint_dir:
                with span("checkpoint", metrics=self.metrics.sink_or_none()):
                    self._ckpt.save(self.state, self._global_step())
        finally:
            # Drains the in-flight write even on an exceptional exit, so
            # its failure re-raises (chained) instead of dying with the
            # worker thread; on the normal path this is the usual close.
            if self._ckpt is not None:
                self._ckpt.close()
            # A fault that ABORTED the loop (injected crash) fired after
            # the last in-loop drain: flush its event here so the obs
            # stream records the fault in the attempt that hit it.
            self._drain_fault_events()
        if not (cfg.eval_every and cfg.epochs > start_epoch
                and cfg.epochs % cfg.eval_every == 0):
            ntests, ncorrect = self.evaluate()
            result_acc = ncorrect / ntests

        ntests = len(self.test_x)
        # The reference's one benchmark line (cnn.c:518).
        self.log.info("ntests=%d, ncorrect=%d", ntests, ncorrect)
        return TrainResult(
            epochs_run=cfg.epochs - start_epoch,
            final_step=self._global_step(),
            test_accuracy=result_acc,
            ntests=ntests,
            ncorrect=ncorrect,
            epoch_seconds=epoch_seconds,
            mean_step_ms=timer.mean_step_ms,
        )

    # ------------------------------------------------------------------

    def evaluate(self, params=None) -> tuple[int, int]:
        """Forward argmax sweep over the test set (cnn.c:494-518).
        Returns (ntests, ncorrect). Pads the tail batch; padding rows are
        excluded from the count."""
        if params is None:
            params = (
                self.state["flat_params"] if self.n_pipe > 1
                else self.state["params"]
            )
        n = len(self.test_x)
        b = self._eval_batch
        ncorrect = 0
        for start in range(0, n, b):
            chunk = self.test_x[start : start + b]
            valid = len(chunk)
            if valid < b:
                pad = np.zeros((b - valid, *chunk.shape[1:]), chunk.dtype)
                chunk = np.concatenate([chunk, pad])
            if self.n_pipe > 1:
                x_mb = jnp.asarray(chunk).reshape(
                    (self._pp_M, -1) + chunk.shape[1:]
                )
                logits = jax.device_get(
                    self.eval_step(params, pp_shard_batch(x_mb, self.mesh))
                ).reshape(b, -1)
            else:
                x = dp_shard_batch(jnp.asarray(chunk), self.mesh)
                logits = jax.device_get(self.eval_step(params, x))
            pred = np.argmax(logits[:valid], axis=-1)
            ncorrect += int((pred == self.test_labels[start : start + valid]).sum())
        return n, ncorrect
