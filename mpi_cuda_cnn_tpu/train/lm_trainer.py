"""End-to-end LM trainer: corpus -> trained TransformerLM.

The product form of the long-context path (train/lm.py has the step;
this has the loop): char-level corpus, random-window batches, train/eval
split, checkpointing, and the parallelism surface — a mesh with a 'data'
and/or 'seq' axis. With a 'seq' axis the step is the sequence-parallel
shard_map program (parallel/sp.py: ring / ring-flash / Ulysses
attention, MoE blocks expert-parallel over the same axis); without one
it is the plain jitted step (data-parallel via GSPMD from the batch
sharding). The CNN Trainer (train/trainer.py) is the reference-parity
loop; this is its twin for the model family the reference never had.
"""

from __future__ import annotations

import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import TransformerLM
from ..obs import cost as obs_cost
from ..obs.device import emit_step_telemetry
from ..obs.trace import span
from ..parallel.dp import replicate
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, PIPE_AXIS, make_mesh
from ..parallel.sp import SEQ_AXIS, make_sp_lm_train_step
from ..faults import (
    MAX_NAN_ROLLBACKS,
    NanGuard,
    NonFiniteLossError,
    PreemptionGuard,
    RollbackToCheckpoint,
    all_finite,
    drain_preemption,
    step_is_finite,
)
from ..obs.metrics import MetricsRegistry
from ..parallel.distributed import barrier, process_info
from ..utils.backend import pallas_interpret
from ..utils.logging import MetricsLogger, get_logger
from ..utils.profiling import StepTimer
from .checkpoint import (
    AsyncCheckpointer,
    restore_latest,
    validate_resume_meta,
)
from .lm import get_attn_fn, lm_loss, make_lm_state, make_lm_train_step, pick_attn_impl
from .optimizer import make_optimizer


def load_corpus(spec: str, package_root: Path | None = None) -> np.ndarray:
    """Resolve a corpus spec to a uint8/int32 token array (char-level).

    "self"      — the framework's own Python sources (real text, zero
                  network: the analog of the digits dataset for the LM).
    "synthetic" — cyclic-successor tokens (deterministic, converges fast).
    a path      — any local text/bytes file.
    """
    if spec == "synthetic":
        return (np.arange(1 << 20) % 251).astype(np.int32)
    if spec == "self":
        root = package_root or Path(__file__).resolve().parents[1]
        parts = [p.read_bytes() for p in sorted(root.rglob("*.py"))]
        data = b"\n".join(parts)
    else:
        data = Path(spec).read_bytes()
    if len(data) < 1 << 12:
        raise ValueError(f"corpus {spec!r} too small: {len(data)} bytes")
    return np.frombuffer(data, np.uint8).astype(np.int32)


def _pick_ring_impl(seq_len: int, n_seq: int) -> str:
    """Shared auto rule for the sequence-parallel fold: the fused flash
    kernel on the TPU with 128-aligned per-shard sequences (its block
    granularity), the plain jnp ring where Pallas would be interpreted
    (platform cpu) or the alignment fails. One definition for the SP
    and TP x SP branches — the two must never drift."""
    if pallas_interpret() or (seq_len // n_seq) % 128:
        return "ring"
    return "ring_flash"


@dataclasses.dataclass
class LMResult:
    steps_run: int
    final_loss: float
    eval_loss: float
    eval_ppl: float
    tokens_per_s: float


class LMTrainer:
    """tokens (int32 stream) + config -> trained params.

    Batches are random (seq_len+1)-windows of the training stream; eval
    is mean NLL over deterministic windows of the held-out tail (10%).
    """

    def __init__(self, cfg, *, mesh=None,
                 metrics: MetricsLogger | None = None, faults=None,
                 preempt: PreemptionGuard | None = None, registry=None,
                 clock=None):
        self.cfg = cfg
        self.log = get_logger()
        self.metrics = metrics or MetricsLogger()
        # Runtime metrics registry (ISSUE 6) — same contract as the CNN
        # Trainer's: ONE shared registry across supervisor rebuilds
        # (restart/step totals survive), a private one standalone.
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        # `clock` has the time.perf_counter call shape and is the ONLY
        # time source the run loop and its telemetry fold read — a
        # FakeClock here makes step_ms/tokens_per_s registry values
        # deterministic (the PR-4 contract, same as StepTimer's).
        self._clock = clock if clock is not None else time.perf_counter
        # Fault hooks + NaN/Inf guard (ISSUE 4) — same contract as the
        # CNN Trainer: `faults` is a faults.FaultInjector shared across
        # supervisor restarts; the guard's policy rules are the shared
        # faults.NanGuard (one implementation for both trainers).
        self.faults = faults
        # Preemption guard (ISSUE 5) — same contract as the CNN
        # Trainer's: the CLI installs signal handlers and shares one;
        # the default answers injected `preempt` faults only.
        self._preempt = preempt if preempt is not None else PreemptionGuard()
        self._nan = NanGuard(getattr(cfg, "nan_policy", "off"),
                             getattr(cfg, "nan_max_bad", 3))
        self._finite_fn = jax.jit(all_finite) if self._nan.active else None

        tokens = load_corpus(cfg.corpus)
        vocab = int(tokens.max()) + 1
        split = max(len(tokens) - len(tokens) // 10, cfg.seq_len + 1)
        self.train_tokens = tokens[:split]
        self.eval_tokens = tokens[split:]
        if len(self.train_tokens) < cfg.seq_len + 1:
            raise ValueError(
                f"corpus ({len(tokens)} tokens) shorter than --seq-len "
                f"{cfg.seq_len}"
            )
        # Validate the post-training sample request NOW — its failure
        # after an hours-long run would lose the run's whole purpose.
        if cfg.sample_tokens < 0 or cfg.sample_tokens >= cfg.seq_len:
            raise ValueError(
                f"--sample-tokens {cfg.sample_tokens} must be in "
                f"[0, seq_len {cfg.seq_len}) — the prompt needs >= 1 "
                f"position of the decode budget"
            )
        if cfg.decode_cache_dtype not in ("float32", "bfloat16", "int8",
                                          "auto"):
            # Same rationale: the auto-generated flag parser is type=str,
            # so a typo ('bf16') would otherwise surface only at
            # sampling time, after the whole run. "auto" (VERDICT 7)
            # routes from the banked int8 table at sample time: int8
            # for GQA/MQA, bfloat16 for MHA (generate.pick_cache_dtype).
            raise ValueError(
                f"--decode-cache-dtype {cfg.decode_cache_dtype!r} must "
                "be 'float32', 'bfloat16', 'int8', or 'auto'"
            )
        if cfg.decode_weights_dtype not in ("float32", "bfloat16",
                                            "int8", "auto"):
            # Same early-validation contract as decode_cache_dtype: the
            # auto-generated parser is type=str, so a typo would
            # otherwise surface only at sampling time. "auto" routes
            # int8 for GQA/MQA, f32 for MHA (pick_weights_dtype — one
            # routing table with the cache's).
            raise ValueError(
                f"--decode-weights-dtype {cfg.decode_weights_dtype!r} "
                "must be 'float32', 'bfloat16', 'int8', or 'auto'"
            )
        if cfg.sample_top_k < 0 or not 0.0 <= cfg.sample_top_p <= 1.0:
            raise ValueError(
                f"--sample-top-k {cfg.sample_top_k} must be >= 0 and "
                f"--sample-top-p {cfg.sample_top_p} in [0, 1]"
            )
        if (cfg.sample_top_k or cfg.sample_top_p) and \
                cfg.sample_temperature <= 0:
            raise ValueError(
                "--sample-top-k/--sample-top-p restrict SAMPLING — set "
                "--sample-temperature > 0 (greedy already takes the "
                "single most likely token)"
            )
        if cfg.sample_speculative_k:
            if cfg.sample_speculative_k < 2:
                raise ValueError(
                    f"--sample-speculative-k {cfg.sample_speculative_k} "
                    "must be >= 2 (the verify block needs proposals)"
                )
            # --sample-temperature > 0 composes since round 5: the
            # speculative path rejection-samples, output law == plain
            # temperature sampling's (models/generate.py).
            if cfg.sample_tokens and cfg.sample_tokens + \
                    cfg.sample_speculative_k + 2 > cfg.seq_len:
                # The same fail-NOW rationale as the checks above: the
                # verify block needs k positions of cache slack beyond
                # prompt (>= 2) + tokens, and sample() runs after the
                # whole training run.
                raise ValueError(
                    f"--sample-tokens {cfg.sample_tokens} + speculative "
                    f"slack k={cfg.sample_speculative_k} + a >= 2-token "
                    f"prompt exceeds seq_len {cfg.seq_len}"
                )

        self.model = TransformerLM(
            vocab=vocab, dim=cfg.dim, heads=cfg.heads, depth=cfg.depth,
            max_seq=cfg.seq_len, moe_experts=cfg.moe_experts,
            moe_top_k=cfg.moe_top_k, kv_heads=cfg.kv_heads, pos=cfg.pos,
        )

        ndev = cfg.num_devices or len(jax.devices())
        if mesh is None:
            from ..utils.config import parse_mesh_shape

            axes = parse_mesh_shape(cfg.mesh_shape, ndev)
            mesh = make_mesh(axes, devices=jax.devices()[:ndev])
        self.mesh = mesh
        from ..parallel.ep import EXPERT_AXIS

        self.n_seq = self.mesh.shape.get(SEQ_AXIS, 1)
        self.n_data = self.mesh.shape.get(DATA_AXIS, 1)
        self.n_model = self.mesh.shape.get(MODEL_AXIS, 1)
        self.n_pipe = self.mesh.shape.get(PIPE_AXIS, 1)
        self.n_expert = self.mesh.shape.get(EXPERT_AXIS, 1)
        if self.n_expert > 1 and (self.n_seq > 1 or self.n_model > 1
                                  or self.n_pipe > 1 or cfg.fsdp):
            raise ValueError(
                "an 'expert' mesh axis composes with 'data' only "
                "(EP x DP, parallel/ep.py make_ep_lm_train_step); MoE "
                "under a 'seq' axis rides EP x SP instead — drop the "
                "other axes/--fsdp or the expert axis"
            )
        if cfg.batch_size % (self.n_data * self.n_expert):
            raise ValueError(
                f"batch_size {cfg.batch_size} not divisible by "
                f"data x expert shards ({self.n_data} x {self.n_expert})"
            )
        if cfg.moe_dispatch_chunk and (
            self.n_expert > 1 or self.n_seq > 1 or self.n_model > 1
            or self.n_pipe > 1
        ):
            raise ValueError(
                "--moe-dispatch-chunk is the SINGLE-DEVICE (or pure-DP) "
                "quadratic-dispatch lever; expert/seq/model/pipe meshes "
                "already shard the routed tokens — drop one of the two"
            )
        if cfg.moe_dispatch_chunk and not cfg.moe_experts:
            raise ValueError(
                "--moe-dispatch-chunk needs an MoE model (--moe-experts)"
            )
        if cfg.moe_dispatch_dtype:
            if not cfg.moe_experts:
                raise ValueError(
                    "--moe-dispatch-dtype needs an MoE model "
                    "(--moe-experts)"
                )
            if cfg.moe_dispatch_dtype not in ("bfloat16", "float32"):
                raise ValueError(
                    f"--moe-dispatch-dtype {cfg.moe_dispatch_dtype!r} "
                    "must be 'bfloat16' or 'float32'"
                )
            if self.n_expert > 1 or self.n_seq > 1 or self.n_pipe > 1:
                # Only the plain jitted step (data/model/FSDP GSPMD
                # meshes) threads the override; silently dropping it on
                # the shard_map paths would let a run believe bf16
                # dispatch was active while building f32 tensors —
                # reject, same policy as --moe-dispatch-chunk. (Under a
                # bf16 compute path those meshes already build bf16
                # dispatch: it follows x.dtype.)
                raise ValueError(
                    "--moe-dispatch-dtype rides the plain jitted step "
                    "(data/model/FSDP meshes); the expert/seq/pipe "
                    "shard_map steps don't thread it — drop one of the "
                    "two (bf16 compute already gives bf16 dispatch "
                    "there)"
                )
        if self.n_model > 1 and self.n_seq > 1:
            # TP x SP (parallel/tp_sp.py): Megatron inside the ring
            # shard_map. Structural checks (MoE, divisibility) fire at
            # state construction via _check_tp_sp.
            if cfg.fsdp:
                raise ValueError(
                    "--fsdp does not compose with the TP x SP shard_map "
                    "step; drop it or use data:N,model:M"
                )
            allowed = ("auto", "oracle", "ring", "ring_flash", "flash")
            if self.n_pipe == 1:
                allowed += ("ulysses",)  # pipelined stages: ring only
            if cfg.attn_impl not in allowed:
                raise ValueError(
                    f"--attn-impl {cfg.attn_impl!r} is not wired into "
                    "this mesh (TP x SP runs ring/ring_flash/ulysses on "
                    "the local heads; with a 'pipe' axis, ring/"
                    "ring_flash only); use auto"
                )
        if self.n_pipe > 1 and cfg.fsdp:
            raise ValueError(
                "the LM's 'pipe' axis composes with 'data', 'model', and "
                "'seq' (up to the full 4D pipe x model x seq x data mesh; "
                "parallel/pp_lm.py, tp_pp_lm.py) but not with --fsdp; "
                "drop the flag or the pipe axis"
            )
        if self.n_pipe > 1 and cfg.batch_size % (self.n_pipe * self.n_data):
            raise ValueError(
                f"batch_size {cfg.batch_size} not divisible by "
                f"num_microbatches x data-axis "
                f"({self.n_pipe} x {self.n_data})"
            )
        if self.n_pipe > 1 and self.n_seq == 1 and \
                cfg.attn_impl not in ("auto", "oracle", "flash"):
            raise ValueError(
                f"--attn-impl {cfg.attn_impl!r} needs a 'seq' mesh axis "
                "(ring attention shards positions); the pipelined stages "
                "see the full sequence — use auto, flash, or oracle"
            )
        if cfg.batch_size % self.n_data:
            raise ValueError(
                f"batch_size {cfg.batch_size} not divisible by data-axis "
                f"size {self.n_data}"
            )
        if cfg.grad_accum > 1:
            if self.n_pipe > 1 or (self.n_seq > 1 and self.n_model > 1):
                raise ValueError(
                    "--grad-accum is not wired into this mesh: the "
                    "'pipe' axis already accumulates over "
                    "--num-microbatches, and the TP x SP step doesn't "
                    "chunk — drop the flag or those axes (plain/TP/"
                    "FSDP/SP/EP meshes all accept it)"
                )
            per_shard = cfg.batch_size // (self.n_data * self.n_expert)
            if per_shard % cfg.grad_accum:
                raise ValueError(
                    f"per-shard batch {per_shard} not divisible by "
                    f"grad_accum {cfg.grad_accum}"
                )
        if cfg.seq_len % self.n_seq:
            raise ValueError(
                f"seq_len {cfg.seq_len} not divisible by seq-axis size "
                f"{self.n_seq}"
            )
        if cfg.fsdp and self.n_data <= 1:
            # Structural mesh check belongs here, before any
            # step/optimizer construction — the user should see the mesh
            # error first. (fsdp + 'seq' composes: ZeRO x ring inside
            # the SP shard_map, parallel/sp.py state_specs.)
            raise ValueError(
                "--fsdp needs a 'data' mesh axis of size > 1 "
                f"(mesh_shape={cfg.mesh_shape!r})"
            )
        if cfg.elastic_width:
            # Elastic (width-invariant) training rides the pure-DP
            # shard_map step only — sharded-param layouts change WHAT
            # is reduced when the width changes, and the dispatch-dtype
            # knobs aren't threaded through the elastic body.
            from ..parallel.elastic import check_elastic_width

            if (self.n_seq > 1 or self.n_model > 1 or self.n_pipe > 1
                    or self.n_expert > 1 or cfg.fsdp):
                raise ValueError(
                    "--elastic-width needs a pure data-parallel mesh "
                    f"(mesh_shape={cfg.mesh_shape!r}/--fsdp shard the "
                    "state; cross-width bitwise resume is only defined "
                    "for replicated params)"
                )
            if cfg.grad_accum > 1:
                raise ValueError(
                    "--elastic-width already scans canonical "
                    "microbatches; --grad-accum is redundant with it"
                )
            if cfg.moe_dispatch_chunk or cfg.moe_dispatch_dtype:
                raise ValueError(
                    "--moe-dispatch-chunk/--moe-dispatch-dtype ride the "
                    "plain jitted step; the elastic shard_map step does "
                    "not thread them — drop one of the two"
                )
            check_elastic_width(cfg.elastic_width, cfg.batch_size,
                                self.n_data)

        # Cosine needs positive decay_steps: clamp warmup only when it
        # would swallow the whole (short) run, and say so.
        warmup = cfg.warmup_steps
        if warmup >= cfg.steps:
            warmup = max(cfg.steps - 1, 0)
            self.log.warning(
                "warmup_steps %d >= steps %d; clamped to %d",
                cfg.warmup_steps, cfg.steps, warmup,
            )
        # The pipelined, Megatron x ring, and ZeRO x ring steps clip
        # IN-STEP with a cross-rank-correct global norm (their params
        # are sharded, so optax's per-rank clip_by_global_norm would
        # compute a partial norm); everywhere else the optax transform
        # does it.
        clip_in_step = self.n_pipe > 1 or self.n_seq > 1 and (
            self.n_model > 1 or cfg.fsdp
        )
        self.optimizer = make_optimizer(
            cfg.lr, opt="adamw", schedule=cfg.lr_schedule,
            total_steps=cfg.steps or None, warmup_steps=warmup,
            weight_decay=cfg.weight_decay,
            grad_clip=0.0 if clip_in_step else cfg.grad_clip,
        )
        compute_dtype = (
            jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else None
        )
        self._compute_dtype = compute_dtype

        if cfg.ce_chunk and (cfg.seq_len // self.n_seq) % cfg.ce_chunk:
            raise ValueError(
                f"--ce-chunk {cfg.ce_chunk} must divide the per-shard "
                f"sequence {cfg.seq_len // self.n_seq} (seq_len "
                f"{cfg.seq_len} over seq:{self.n_seq})"
            )
        if self.n_pipe > 1:
            # GPipe over stacked transformer blocks (parallel/pp_lm.py):
            # blocks stage-sharded over 'pipe', microbatches over 'data'.
            from ..parallel.pp_lm import (
                make_pp_lm_state,
                make_pp_lm_train_step,
            )

            params = self.model.init(jax.random.key(cfg.seed))
            if self.n_seq > 1:
                # SP x PP (x DP): long sequences THROUGH a pipelined
                # model — ring attention inside each GPipe stage; with a
                # 'model' axis too, the FULL 4D mesh (Megatron blocks,
                # ring on the local heads).
                impl = cfg.attn_impl
                if impl in ("auto", "flash"):
                    impl = _pick_ring_impl(cfg.seq_len, self.n_seq)
                elif impl == "oracle":
                    impl = "ring"
                self.attn_impl = impl
                if self.n_model > 1:
                    from ..parallel.tp_pp_lm import (
                        make_tp_pp_lm_state,
                        make_tp_pp_lm_train_step,
                    )

                    self.state = make_tp_pp_lm_state(
                        self.model, params, self.optimizer, self.mesh
                    )
                    self.train_step = make_tp_pp_lm_train_step(
                        self.model, self.optimizer, self.mesh, self.state,
                        compute_dtype=compute_dtype, remat=cfg.remat,
                        grad_clip=cfg.grad_clip, attn_impl=impl,
                        ce_chunk=cfg.ce_chunk, donate=cfg.donate,
                    )
                else:
                    from ..parallel.pp_lm import make_sp_pp_lm_train_step

                    self.state = make_pp_lm_state(
                        self.model, params, self.optimizer, self.mesh
                    )
                    self.train_step = make_sp_pp_lm_train_step(
                        self.model, self.optimizer, self.mesh, self.state,
                        compute_dtype=compute_dtype, remat=cfg.remat,
                        grad_clip=cfg.grad_clip, impl=impl,
                        ce_chunk=cfg.ce_chunk, donate=cfg.donate,
                    )
            else:
                # Each stage sees the full sequence, so the plain
                # attention router applies unchanged — flash per stage
                # on TPU.
                self.attn_impl = pick_attn_impl(
                    cfg.attn_impl, cfg.seq_len, compute_dtype
                )
                if self.n_model > 1:
                    # TP x PP (x DP): Megatron inside the GPipe stages —
                    # the 3D layout (parallel/tp_pp_lm.py).
                    from ..parallel.tp_pp_lm import (
                        make_tp_pp_lm_state as make_state,
                        make_tp_pp_lm_train_step as make_step,
                    )
                else:
                    make_state, make_step = make_pp_lm_state, \
                        make_pp_lm_train_step
                self.state = make_state(
                    self.model, params, self.optimizer, self.mesh
                )
                self.train_step = make_step(
                    self.model, self.optimizer, self.mesh, self.state,
                    compute_dtype=compute_dtype, remat=cfg.remat,
                    grad_clip=cfg.grad_clip, attn_impl=self.attn_impl,
                    ce_chunk=cfg.ce_chunk, donate=cfg.donate,
                )
        elif self.n_seq > 1 and self.n_model > 1:
            from ..parallel.tp_sp import (
                make_tp_sp_lm_train_step,
                make_tp_sp_state,
            )

            # Honor an explicit choice; "auto"/"flash" use the shared
            # rule, "oracle" maps to the exact jnp ring.
            impl = cfg.attn_impl
            if impl in ("auto", "flash"):
                impl = _pick_ring_impl(cfg.seq_len, self.n_seq)
            elif impl == "oracle":
                impl = "ring"
            self.attn_impl = impl
            params = self.model.init(jax.random.key(cfg.seed))
            self.state, specs = make_tp_sp_state(
                self.model, params, self.optimizer, self.mesh
            )
            self.train_step = make_tp_sp_lm_train_step(
                self.model, self.optimizer, self.mesh, specs,
                data_axis=DATA_AXIS if self.n_data > 1 else None,
                compute_dtype=compute_dtype, remat=cfg.remat,
                ce_chunk=cfg.ce_chunk, impl=self.attn_impl,
                grad_clip=cfg.grad_clip, donate=cfg.donate,
            )
        elif self.n_expert > 1:
            # EP x DP: batch sharded over (data, expert) jointly; the
            # MoE dispatch all_to_alls over 'expert' inside the step.
            from ..parallel.ep import make_ep_lm_train_step

            self.attn_impl = pick_attn_impl(
                cfg.attn_impl, cfg.seq_len, compute_dtype
            )
            self.train_step = make_ep_lm_train_step(
                self.model, self.optimizer, self.mesh,
                data_axis=DATA_AXIS if self.n_data > 1 else None,
                attn_impl=self.attn_impl, remat=cfg.remat,
                compute_dtype=compute_dtype, ce_chunk=cfg.ce_chunk,
                grad_accum=cfg.grad_accum, donate=cfg.donate,
            )
        elif self.n_seq > 1:
            impl = cfg.attn_impl
            if impl in ("auto", "flash"):
                impl = _pick_ring_impl(cfg.seq_len, self.n_seq)
            elif impl == "oracle":
                impl = "ring"
            self.attn_impl = impl
            sp_specs = None
            if cfg.fsdp:
                # ZeRO x ring: state placed by the generic FSDP specs
                # (largest dim over 'data'); the step consumes the
                # placement's own spec tree, so the two cannot disagree.
                from ..parallel.fsdp import make_fsdp_state, state_specs

                params = self.model.init(jax.random.key(cfg.seed))
                self.state = make_fsdp_state(
                    params, self.optimizer, self.mesh
                )
                sp_specs = state_specs(self.state)
            self.train_step = make_sp_lm_train_step(
                self.model, self.optimizer, self.mesh, impl=impl,
                data_axis=DATA_AXIS if self.n_data > 1 else None,
                remat=cfg.remat, compute_dtype=compute_dtype,
                ce_chunk=cfg.ce_chunk, state_specs=sp_specs,
                grad_clip=cfg.grad_clip if cfg.fsdp else 0.0,
                grad_accum=cfg.grad_accum, donate=cfg.donate,
            )
        elif cfg.elastic_width:
            # Width-invariant canonical-tree DP (ISSUE 5): the explicit
            # shard_map step whose trajectory is bitwise identical on
            # any supported data width — what makes a preempted run
            # resumable on a different topology (train/lm.py).
            from .lm import make_elastic_lm_train_step

            self.train_step, self.attn_impl = make_elastic_lm_train_step(
                self.model, self.optimizer, self.mesh,
                elastic_width=cfg.elastic_width, attn_impl=cfg.attn_impl,
                seq_len=cfg.seq_len, compute_dtype=compute_dtype,
                remat=cfg.remat, ce_chunk=cfg.ce_chunk,
                donate=cfg.donate,
            )
        else:
            self.attn_impl = pick_attn_impl(
                cfg.attn_impl, cfg.seq_len, compute_dtype
            )
            self.train_step = make_lm_train_step(
                self.model, self.optimizer, attn_impl=self.attn_impl,
                seq_len=cfg.seq_len, compute_dtype=compute_dtype,
                remat=cfg.remat, ce_chunk=cfg.ce_chunk,
                grad_accum=cfg.grad_accum,
                moe_dispatch_chunk=cfg.moe_dispatch_chunk,
                moe_dispatch_dtype=(
                    jnp.dtype(cfg.moe_dispatch_dtype)
                    if cfg.moe_dispatch_dtype else None
                ),
                donate=cfg.donate, mesh=self.mesh,
            )
        if self.n_pipe > 1 or self.n_seq > 1 and (self.n_model > 1
                                                  or cfg.fsdp):
            pass  # state already built above (PP / TP x SP / FSDP x SP)
        elif cfg.fsdp:
            # ZeRO-style sharding for the LM — the same generic spec
            # machinery as the CNN path (parallel/fsdp.py); with a
            # 'model' axis present the TP specs are the base and 'data'
            # takes the largest remaining dim (FSDP x TP). Mesh shape
            # was validated up front with the other structural checks.
            from ..parallel.fsdp import make_fsdp_state

            base = None
            if self.n_model > 1:
                from ..parallel.tp import lm_tp_specs

                base = lm_tp_specs(self.model, self.mesh)
            params = self.model.init(jax.random.key(cfg.seed))
            self.state = make_fsdp_state(
                params, self.optimizer, self.mesh, base_specs=base
            )
        elif self.n_model > 1:
            # Megatron-style TP as GSPMD placement (parallel/tp.py
            # lm_tp_specs): the SAME plain jitted step, params sharded
            # over 'model' — XLA inserts the collectives.
            from ..parallel.tp import make_lm_tp_state

            params = self.model.init(jax.random.key(cfg.seed))
            self.state = make_lm_tp_state(
                self.model, params, self.optimizer, self.mesh
            )
        else:
            self.state = replicate(
                make_lm_state(self.model, self.optimizer, cfg.seed),
                self.mesh,
            )
        self._eval_fn = None
        # Checkpoint topology metadata + multihost write discipline —
        # same scheme as the CNN Trainer (ISSUE 5): manifest records
        # the mesh/elastic width per checkpoint, process 0 is the only
        # writer, a barrier fences publication.
        from ..parallel.mesh import describe_mesh

        self._proc = process_info()
        self._ckpt_meta = {
            "mesh": describe_mesh(self.mesh),
            "elastic_width": cfg.elastic_width,
            "process_count": self._proc.process_count,
        }
        self._ckpt = (
            AsyncCheckpointer(cfg.checkpoint_dir,
                              async_=cfg.async_checkpoint, faults=faults,
                              meta=self._ckpt_meta, process=self._proc,
                              barrier=barrier)
            if cfg.checkpoint_dir else None
        )

    # ------------------------------------------------------------------

    def _sample_batch(self, step: int):
        """(B, S) inputs + targets: random windows of the train stream.

        The RNG is derived from (seed, step), not a stream advanced from
        cfg.seed, so a run resumed at step k sees exactly the windows the
        uninterrupted run would have seen at steps k, k+1, ... — the same
        step-exact-resume contract the CNN trainer keeps with its
        (seed, epoch)-derived shuffle order.
        """
        cfg = self.cfg
        # A window consumes seq_len+1 tokens; valid starts are
        # [0, len - seq_len - 1] inclusive, so the exclusive high bound is
        # len - seq_len (== 1 for the minimal corpus the ctor accepts).
        n = len(self.train_tokens) - cfg.seq_len
        rng = np.random.default_rng((cfg.seed, step))
        starts = rng.integers(0, n, size=cfg.batch_size)
        idx = starts[:, None] + np.arange(cfg.seq_len + 1)[None, :]
        w = self.train_tokens[idx]
        return jnp.asarray(w[:, :-1]), jnp.asarray(w[:, 1:])

    def _place(self, t):
        """Shard (B, S) over (data, seq) mesh axes — or microbatch to
        (M, mb, S) with mb over 'data' on the pipelined mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if self.n_pipe > 1:
            from ..parallel.pp_lm import (
                pp_lm_shard_batch,
                sp_pp_shard_batch,
            )

            t = t.reshape((self.n_pipe, -1) + t.shape[1:])
            place = (sp_pp_shard_batch if self.n_seq > 1
                     else pp_lm_shard_batch)
            return place(t, self.mesh)
        from ..parallel.ep import EXPERT_AXIS

        batch_axes = tuple(
            a for a, n in ((DATA_AXIS, self.n_data),
                           (EXPERT_AXIS, self.n_expert)) if n > 1
        )
        spec = P(
            batch_axes if len(batch_axes) > 1
            else (batch_axes[0] if batch_axes else None),
            SEQ_AXIS if self.n_seq > 1 else None,
        )
        return jax.device_put(t, NamedSharding(self.mesh, spec))

    def _standard_layout(self) -> bool:
        """True when the live state's params are already the standard
        tree (DP / TP / FSDP / SP placements) — eval and decode can run
        straight off the placement, GSPMD partitioning them; the packed
        (PP) and head-structured (TP x SP) layouts need _host_params."""
        p = self.state["params"]
        return "rest" not in p and not (
            p["blocks"] and p["blocks"][0]["wo"].ndim == 3
        )

    def _host_params(self):
        """Host copy of the params in the STANDARD tree layout: the
        pipelined state stores stacked blocks (unstack), the TP x SP
        state stores head-structured weights (un-reshape) — eval and
        decode consume the standard tree either way."""
        p = jax.device_get(self.state["params"])
        if "rest" in p:
            # Stacked wo is (L, h*hd, d); the TP x PP packed layout is
            # additionally head-structured: (L, H, hd, d).
            if p["blocks"]["wo"].ndim == 4:
                from ..parallel.tp_pp_lm import unstack_tp_blocks

                p = unstack_tp_blocks(p, self.model)
            else:
                from ..parallel.pp_lm import unstack_blocks

                p = unstack_blocks(p, self.model.depth)
        elif p["blocks"] and p["blocks"][0]["wo"].ndim == 3:
            from ..parallel.tp_sp import from_tp_layout

            p = from_tp_layout(p, self.model)
        return p

    def _place_host_state(self, host_state) -> None:
        """Install a host-side state pytree with the live shardings."""
        shardings = jax.tree.map(lambda a: a.sharding, self.state)
        self.state = jax.device_put(host_state, shardings)

    def _drop_bad_update(self, step: int, snap) -> None:
        """Apply --nan-policy to a non-finite step (faults.NanGuard owns
        the rules; abort and rollback raise there). A plain skip drops
        the update by reinstalling the pre-step snapshot with the step
        counter ADVANCED past the dropped batch — state["step"] must
        equal batches consumed, or a crash-restart would resume short by
        the skipped steps (see Trainer._drop_bad_update)."""
        self._nan.bad_step(step, logger=self.log, metrics=self.metrics)
        snap = dict(snap)
        snap["step"] = np.asarray(snap["step"]) + 1
        self._place_host_state(snap)

    def _rollback_to_checkpoint(self) -> int:
        """Reload the newest valid checkpoint after a nan-policy=restore
        rollback; returns the step to re-enter at."""
        if self._ckpt is not None:
            self._ckpt.wait()  # the in-flight write may be newest
        restored, path = restore_latest(
            self.cfg.checkpoint_dir, jax.device_get(self.state),
            logger=self.log, metrics=self.metrics,
        ) if self.cfg.checkpoint_dir else (None, None)
        if restored is None:
            raise NonFiniteLossError(
                "nan-policy=restore: no valid checkpoint to roll "
                "back to (set --checkpoint-dir/--checkpoint-every)"
            )
        self._place_host_state(restored)
        self._nan.step_ok()
        step0 = int(jax.device_get(self.state["step"]))
        self.metrics.log("fault", kind="nan_restore", step=step0,
                         path=path.name)
        self.log.warning("nan-policy=restore: rolled back to %s (step %d)",
                         path, step0)
        return step0

    def _step_boundary(self, global_step: int) -> None:
        """Per-step fault/preemption hook (the CNN Trainer's twin): an
        injected ``preempt`` fault sets the same flag a real SIGTERM
        would; a pending preemption then drains the shared orderly exit
        (faults.drain_preemption)."""
        if self.faults is not None:
            for f in self.faults.fire("train.step", global_step):
                if f.kind == "preempt":
                    self._preempt.request()
            for ev in self.faults.drain_events():
                self.metrics.log("fault", **ev)
        drain_preemption(self._preempt, state=self.state,
                         global_step=global_step, ckpt=self._ckpt,
                         metrics=self.metrics, logger=self.log)

    def train(self) -> LMResult:
        cfg = self.cfg
        start_step = 0
        if cfg.resume and cfg.checkpoint_dir:
            host = jax.device_get(self.state)
            # restore_latest verifies manifest checksums and falls back
            # past corrupt files to the newest valid checkpoint.
            restored, ckpt = restore_latest(cfg.checkpoint_dir, host,
                                            logger=self.log,
                                            metrics=self.metrics)
            if restored is not None:
                validate_resume_meta(ckpt, mesh=self.mesh,
                                     elastic_width=cfg.elastic_width,
                                     metrics=self.metrics, logger=self.log)
                shardings = jax.tree.map(lambda a: a.sharding, self.state)
                self.state = jax.device_put(restored, shardings)
                # The resumed-from checkpoint must survive later prunes
                # — it is the only valid restore point until the next
                # save lands.
                if self._ckpt is not None:
                    self._ckpt.protect = ckpt.name
                start_step = int(jax.device_get(self.state["step"]))
                self.metrics.log("ckpt", step=start_step, reason="resume",
                                 path=ckpt.name)
                self.log.info("resumed from %s at step %d", ckpt, start_step)
                # A checkpoint past --steps means nothing left to run; the
                # loop below is empty and steps_run clamps to 0.
                start_step = min(start_step, cfg.steps)

        t0 = self._clock()
        loss = float("nan")
        m = None
        timer = StepTimer(clock=self._clock)
        timer.start()
        logged_cost = False
        rollbacks = 0
        # Per-interval registry anchors (ISSUE 6): each log interval
        # folds its step-time mean and tokens/s into the runtime
        # registry, excluding the one-off obs AOT compile the timer
        # already excludes from its own envelope.
        last_t, last_step, last_exc = t0, start_step, 0.0
        try:
            step = start_step
            while step < cfg.steps:
                with timer.phase("data"):
                    tokens, targets = self._sample_batch(step)
                    tokens, targets = self._place(tokens), self._place(targets)
                if not logged_cost and self.metrics.jsonl_enabled:
                    logged_cost = True
                    # exclude(): the analysis costs an AOT compile that
                    # must not land in the step-phase attribution.
                    with timer.exclude():
                        if not obs_cost.log_program(
                            self.metrics, "lm_train_step", self.train_step,
                            self.state, tokens, targets,
                            compute_dtype=cfg.compute_dtype,
                        ):
                            self.log.warning(
                                "obs: cost analysis unavailable for "
                                "lm_train_step"
                            )
                # skip/restore must drop the bad update — hold the
                # pre-step state on host (donation consumes the buffers).
                snap = (jax.device_get(self.state)
                        if self._nan.snapshots else None)
                with timer.phase("dispatch"):
                    self.state, m = self.train_step(self.state, tokens, targets)
                try:
                    if self._nan.active and not step_is_finite(
                        m, self._finite_fn, self.state
                    ):
                        # Drop the update (abort/rollback raise); the
                        # checkpoint + crash hooks below still run — a
                        # skipped step consumed its batch, and a planned
                        # fault at this step value must not evaporate.
                        self._drop_bad_update(step, snap)
                    else:
                        self._nan.step_ok()
                        if cfg.log_every and (step + 1) % cfg.log_every == 0:
                            with timer.phase("device"):
                                loss = float(m["loss"])
                            self.metrics.log("train", step=step + 1,
                                             loss=loss)
                            now = self._clock()
                            n = step + 1 - last_step
                            dt = (now - last_t
                                  - (timer.excluded_s - last_exc))
                            if n > 0 and dt > 0:
                                reg = self.registry
                                reg.inc("train.steps", n)
                                reg.inc("train.heartbeats")
                                reg.observe("train.step_ms", 1e3 * dt / n)
                                reg.set(
                                    "train.tokens_per_s",
                                    n * cfg.batch_size * cfg.seq_len / dt,
                                )
                                # Loss gauge (ISSUE 8): health/top read
                                # it off `metrics` snapshots with its
                                # min/max envelope.
                                reg.set("train.loss", loss)
                                reg.emit(self.metrics, step=step + 1)
                            last_t, last_step = now, step + 1
                            last_exc = timer.excluded_s
                except RollbackToCheckpoint:
                    rollbacks += 1
                    if rollbacks > MAX_NAN_ROLLBACKS:
                        raise NonFiniteLossError(
                            f"nan-policy=restore: rolled back "
                            f"{MAX_NAN_ROLLBACKS} times and the run "
                            "still goes non-finite"
                        ) from None
                    step = self._rollback_to_checkpoint()
                    continue
                if cfg.checkpoint_dir and cfg.checkpoint_every and (
                    (step + 1) % cfg.checkpoint_every == 0
                ):
                    with timer.phase("checkpoint"):
                        self._ckpt.save(self.state, step + 1)
                self._step_boundary(step + 1)
                step += 1
            with timer.phase("device"):
                jax.block_until_ready(self.state)
            # Exclude the obs AOT compile from the headline tokens/s —
            # telemetry must not sink the number it reports.
            dt = self._clock() - t0 - timer.excluded_s
            if cfg.checkpoint_dir:
                self._ckpt.save(self.state, cfg.steps)
        finally:
            # Even on an exceptional exit the in-flight write drains and
            # its failure re-raises (chained) — it cannot be dropped.
            if self._ckpt is not None:
                self._ckpt.close()
            # Flush fault events fired after the last in-loop drain
            # (e.g. the injected crash that aborted this attempt).
            if self.faults is not None:
                for ev in self.faults.drain_events():
                    self.metrics.log("fault", **ev)
        steps_run = cfg.steps - start_step
        loss = float(m["loss"]) if m is not None else loss
        timer.stop(max(steps_run, 1))
        emit_step_telemetry(self.metrics, timer, steps_run,
                            devices=list(self.mesh.devices.flat))
        if steps_run > 0:
            # Final registry snapshot: the headline tokens/s (same dt
            # the LMResult reports) plus any tail steps the log-interval
            # anchors missed.
            reg = self.registry
            if cfg.steps > last_step:
                reg.inc("train.steps", cfg.steps - last_step)
            reg.set("train.tokens_per_s",
                    steps_run * cfg.batch_size * cfg.seq_len
                    / max(dt, 1e-9))
            reg.emit(self.metrics, final=True)

        with span("eval", metrics=self.metrics.sink_or_none()):
            eval_loss = self.evaluate()
        tok_s = steps_run * cfg.batch_size * cfg.seq_len / max(dt, 1e-9)
        self.log.info(
            "lm done: steps=%d loss=%.4f eval_loss=%.4f ppl=%.2f tok/s=%.0f",
            steps_run, loss, eval_loss, float(np.exp(eval_loss)), tok_s,
        )
        return LMResult(
            steps_run=steps_run,
            final_loss=loss,
            eval_loss=eval_loss,
            eval_ppl=float(np.exp(eval_loss)),
            tokens_per_s=tok_s,
        )

    # ------------------------------------------------------------------

    def sample(self, num_tokens: int, *, prompt_len: int | None = None,
               temperature: float = 0.0, seed: int = 0):
        """Generate a continuation of the held-out stream with the
        KV-cache decode path (models/generate.py) — the product surface
        of inference: prompt from the eval tail, greedy by default.

        Returns (prompt, continuation) as int32 numpy arrays; the CLI
        decodes them as bytes for char-level corpora.
        """
        from ..models.generate import generate

        cfg = self.cfg
        # Speculative decoding needs k positions of cache slack beyond
        # prompt + num_tokens (the verify block may overshoot); shrink
        # the prompt, not k.
        spec_k = cfg.sample_speculative_k
        max_prompt = cfg.seq_len - num_tokens - spec_k
        if max_prompt < (2 if spec_k else 1):
            raise ValueError(
                f"--sample-tokens {num_tokens}"
                + (f" + speculative slack k={spec_k}" if spec_k else "")
                + f" leaves no room for a prompt within seq_len "
                f"{cfg.seq_len}"
            )
        p = min(prompt_len or max(cfg.seq_len // 2, 1), max_prompt)
        stream = (
            self.eval_tokens if len(self.eval_tokens) >= p
            else self.train_tokens
        )
        prompt = jnp.asarray(np.asarray(stream[:p])[None, :], jnp.int32)
        if self._standard_layout():
            # Decode STRAIGHT off the live placement — GSPMD partitions
            # the scan from it (sharded serving), no host round-trip.
            params = self.state["params"]
        else:
            # Packed (PP) / head-structured (TP x SP) layouts: convert
            # on host, then re-place with the Megatron TP shardings when
            # the mesh has a model axis (KV cache head-sharded).
            params = self._host_params()
            if self.n_model > 1:
                from ..parallel.tp import shard_lm_params

                params = shard_lm_params(self.model, params, self.mesh)
        wdt = self._weights_dtype()
        if wdt != "float32":
            # One-time serving-weights conversion (ISSUE 12): int8
            # per-channel QuantW / bf16 cast through the SAME forward
            # (qmatmul dispatch). Single-placement paths only — the
            # QuantW leaves don't carry Megatron shardings, and a
            # sample-time lever must not silently unshard the decode.
            if self.n_model > 1:
                raise ValueError(
                    "--decode-weights-dtype requires an unsharded "
                    "sample path (model-parallel decode keeps f32 "
                    "weights; set --decode-weights-dtype float32)"
                )
            from ..ops.pallas_gemv import quantize_decode_params

            params = quantize_decode_params(params, wdt)
        if cfg.sample_speculative_k:
            # Draft-free prompt-lookup speculation. Greedy at
            # temperature 0 (bitwise-exact contract); temperature > 0
            # runs rejection sampling — output law == plain sampling's
            # (models/generate.py _spec_sample_rows).
            if p < 2:
                # The lookup ngram (default 2) needs that much prompt;
                # fail here with the config's vocabulary rather than
                # deeper with the generator's (ADVICE round-4 finding).
                raise ValueError(
                    f"--sample-speculative-k needs a prompt of >= 2 "
                    f"tokens (resolved prompt length {p}; raise "
                    f"prompt_len or seq_len)"
                )
            from ..models.generate import lookup_speculative_generate

            toks = lookup_speculative_generate(
                self.model, params, prompt, num_tokens,
                k=cfg.sample_speculative_k,
                cache_dtype=self._cache_dtype(),
                temperature=temperature,
                key=jax.random.key(seed) if temperature > 0 else None,
                top_k=cfg.sample_top_k, top_p=cfg.sample_top_p,
            )
        else:
            toks = generate(
                self.model, params, prompt, num_tokens,
                temperature=temperature,
                key=jax.random.key(seed) if temperature > 0 else None,
                cache_dtype=self._cache_dtype(),
                top_k=cfg.sample_top_k, top_p=cfg.sample_top_p,
            )
        return np.asarray(prompt[0]), np.asarray(toks[0])

    def _cache_dtype(self) -> str:
        """--decode-cache-dtype with "auto" resolved against THIS
        model's head geometry (generate.pick_cache_dtype, VERDICT 7)."""
        from ..models.generate import pick_cache_dtype

        return pick_cache_dtype(self.cfg.decode_cache_dtype,
                                heads=self.model.heads,
                                kv_heads=self.model.n_kv)

    def _weights_dtype(self) -> str:
        """--decode-weights-dtype with "auto" resolved against THIS
        model's head geometry (generate.pick_weights_dtype — one
        routing table with the cache's)."""
        from ..models.generate import pick_weights_dtype

        return pick_weights_dtype(self.cfg.decode_weights_dtype,
                                  heads=self.model.heads,
                                  kv_heads=self.model.n_kv)

    def evaluate(self) -> float:
        """Mean next-token NLL over deterministic windows of the held-out
        tail. Standard-layout states feed the LIVE placement into the
        jitted forward (GSPMD partitions it — DP/TP/FSDP/SP); packed and
        head-structured states convert on host first (eval is tiny next
        to training either way)."""
        cfg = self.cfg
        s = cfg.seq_len
        stream = self.eval_tokens
        if len(stream) < s + 1:
            stream = self.train_tokens  # tiny-corpus fallback
        nwin = min(8, (len(stream) - 1) // s)
        if self._eval_fn is None:
            attn_fn = get_attn_fn(
                "flash" if self.attn_impl in ("flash", "ring_flash")
                else "oracle", self.mesh,
            )

            @jax.jit
            def eval_fn(params, tokens, targets):
                # ce_chunk rides along: the batched windows would
                # otherwise materialize (nwin, S, V) f32 logits on
                # exactly the configs the flag exists for.
                return lm_loss(
                    self.model, params, tokens, targets, attn_fn=attn_fn,
                    compute_dtype=self._compute_dtype, moe_aux_weight=0.0,
                    ce_chunk=self.cfg.ce_chunk,
                )

            self._eval_fn = eval_fn
        params = (
            self.state["params"] if self._standard_layout()
            else self._host_params()
        )
        if nwin == 0:
            return float("nan")
        # ONE batched forward over all windows (equal sizes make the
        # batch-mean NLL the mean of per-window means) instead of a
        # dispatch per window — 8x fewer dispatches and host reads, and
        # the eval_fn jit cache sees one shape.
        wins = np.stack([
            np.asarray(stream[i * s : i * s + s + 1]) for i in range(nwin)
        ])
        return float(self._eval_fn(
            params, jnp.asarray(wins[:, :-1]), jnp.asarray(wins[:, 1:])
        ))
