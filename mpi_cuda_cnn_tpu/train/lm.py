"""Language-model training: the transformer's train step + loss.

The reference's training loop is CNN-only (cnn.c:445-474); this module is
its twin for the framework's long-context model family (models/
transformer.py). One jitted step — forward, causal-LM cross-entropy,
backward, optimizer update — with the TPU levers exposed:

- `attn_impl`: "flash" (the fused Pallas kernel pair,
  ops/pallas_attention.py) is the default on TPU; "oracle" is the
  quadratic jnp reference; "auto" picks per backend/shape.
- `compute_dtype`: bfloat16 runs every matmul on the MXU's native path
  (master params stay f32 — mixed precision, not low-precision training).
- `remat`: jax.checkpoint per block (activation memory for FLOPs).

Sequence-parallel training lives in parallel/sp.py (shard_map over a
'seq' axis); this step is the single-device / pure-DP form. For DP, jit
partitions it over the mesh from the state/batch shardings (GSPMD), the
same design as parallel/tp.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..models.transformer import TransformerLM
from ..utils.backend import pallas_interpret
from ..utils.donation import donate_jit


# Measured f32 oracle/flash crossover (scripts/bench_crossover.py on one
# v5e, 2026-07-31, an earlier installation, not re-measured — full f32
# train step at b=2, depth=4, two-point timing, TWO independent
# captures):
#   s=2048: flash 28.2 vs 31.1 ms, then 32.7 vs 30.9  <- flips run-to-run
#   s=3072: flash 61.5 vs 61.6,    then 57.3 vs 57.6  <- flash, both runs
#   s=4096: flash 87.4 vs 91.6,    then 87.8 vs 95.5
#   s=6144: flash 160.4 vs 183.1,  then 161.1 vs 178.0
# The bound sits where flash wins RELIABLY: s=2048 is a coin flip within
# that capture's noise band (bench_lm's b=8/depth=8 matrix also had the
# oracle up 8% there), so it routes to the oracle — also the f32
# accuracy story — and every measured point from 3072 up routes to
# flash. Throughput runs use bf16, where flash wins 2.2x outright at
# every 128-aligned length.
_F32_FLASH_MIN_SEQ = 3072


def pick_attn_impl(impl: str, seq_len: int, compute_dtype=None) -> str:
    """Resolve "auto" to a concrete attention implementation.

    Measurement-driven (PERF.md, one v5e): the fused flash kernel wins
    wherever its block constraint (S % 128 == 0) holds on the TPU
    *except* f32 at short sequences, where the oracle's default-precision
    XLA matmuls beat the f32 kernel's HIGHEST-precision dots — there the
    oracle is both faster and the f32 path's accuracy story. On platform
    cpu, where Pallas is interpreted (orders of magnitude slower than
    XLA — correct, but only for tests), the oracle is the deliberate
    pick; any other platform is an error (utils/backend.pallas_interpret),
    not a quiet oracle.
    """
    if impl != "auto":
        return impl
    if pallas_interpret() or seq_len % 128 != 0:
        return "oracle"
    f32 = compute_dtype is None or jnp.dtype(compute_dtype) == jnp.float32
    if f32 and seq_len < _F32_FLASH_MIN_SEQ:
        return "oracle"
    return "flash"


def get_attn_fn(impl: str, mesh=None):
    """Concrete attention callable (q, k, v) -> o, causal, for `impl`.

    `mesh` is for callers whose step is partitioned by GSPMD (the plain
    jitted LM step on data / model / FSDP meshes, and the trainer's
    eval): XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned. Please wrap the call in a shard_map"
    — the first four-chip run, PR 21; virtual CPU devices never showed
    it because the CPU pick is the oracle), so on a multi-device mesh
    the flash kernel is wrapped in a shard_map over the batch: attention
    is independent per batch row, so each device runs the kernel on its
    own rows. Any other mesh axis sees replicated operands — correct,
    with a gather of the heads a 'model' axis had split (ROADMAP D2).
    A batch the 'data' axis does not divide (a short eval) runs
    replicated. Callers already inside a shard_map pass no mesh."""
    if impl == "flash":
        from ..ops.pallas_attention import flash_attention

        def flash(q, k, v):
            return flash_attention(q, k, v, True)

        if mesh is None or mesh.size == 1:
            return flash

        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS

        n_data = mesh.shape.get(DATA_AXIS, 0)

        def sharded_flash(q, k, v):
            split = n_data and q.shape[0] % n_data == 0
            spec = P(DATA_AXIS if split else None)
            return jax.shard_map(
                flash, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, check_vma=False,
            )(q, k, v)

        return sharded_flash
    if impl == "oracle":
        from ..ops.attention import attention

        return lambda q, k, v: attention(q, k, v, causal=True)
    raise ValueError(
        f"unknown attention impl {impl!r}; use 'flash' or 'oracle' "
        "(resolve 'auto' with pick_attn_impl first)"
    )


def lm_loss(
    model: TransformerLM,
    params,
    tokens,
    targets,
    *,
    attn_fn=None,
    compute_dtype=None,
    remat: bool = False,
    moe_aux_weight: float = 0.01,
    ce_chunk: int = 0,
    moe_axis: str | None = None,
    moe_dispatch_chunk: int = 0,
    moe_dispatch_dtype=None,
):
    """Mean next-token NLL (+ the Switch aux loss when the model is MoE).
    tokens/targets: (B, S) int32. The loss softmax always runs in f32.
    moe_axis names a mesh axis for expert-parallel dispatch inside a
    shard_map caller (parallel/ep.py make_ep_lm_train_step); None keeps
    the local dense dispatch. moe_dispatch_chunk > 0 routes MoE tokens
    in chunks (ep.moe_mlp dispatch_chunk — the single-chip lever for the
    quadratic dispatch-einsum term; incompatible with moe_axis).

    ce_chunk > 0 fuses the head matmul into a chunked cross-entropy: the
    final-LN features go through the head in S-chunks of that size inside
    a lax.scan, each chunk's NLL computed and reduced under
    jax.checkpoint — the (B, S, V) f32 logits are NEVER materialized
    (peak extra memory O(B * chunk * V), recomputed in backward). At
    vocab 8k x s 2k x b 8 the dense logits are 512 MB of HBM traffic; at
    32k+ vocab they stop fitting at all — this is the standard fix.
    ce_chunk must divide S; 0 keeps the dense path.
    """
    if ce_chunk:
        from ..ops.losses import chunked_ce_mean

        feats, aux = model.apply(
            params, tokens, attn_fn=attn_fn, remat=remat,
            compute_dtype=compute_dtype, return_aux=True,
            return_features=True, moe_axis=moe_axis,
            moe_dispatch_chunk=moe_dispatch_chunk,
            moe_dispatch_dtype=moe_dispatch_dtype,
        )
        nll = chunked_ce_mean(
            feats, params["head"], targets, ce_chunk, compute_dtype
        )
        return nll + moe_aux_weight * aux
    logits, aux = model.apply(
        params, tokens, attn_fn=attn_fn, remat=remat,
        compute_dtype=compute_dtype, return_aux=True, moe_axis=moe_axis,
        moe_dispatch_chunk=moe_dispatch_chunk,
        moe_dispatch_dtype=moe_dispatch_dtype,
    )
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return jnp.mean(nll) + moe_aux_weight * aux


def make_lm_train_step(
    model: TransformerLM,
    optimizer,
    *,
    attn_impl: str = "auto",
    seq_len: int | None = None,
    compute_dtype=None,
    remat: bool = False,
    donate: bool = True,
    moe_aux_weight: float = 0.01,
    ce_chunk: int = 0,
    grad_accum: int = 1,
    moe_dispatch_chunk: int = 0,
    moe_dispatch_dtype=None,
    accum_dtype=None,
    mesh=None,
):
    """step(state, tokens, targets) -> (state, {"loss": ...}), jitted.

    mesh: the mesh GSPMD will partition this step over, when there is
    one — only the flash kernel needs it (get_attn_fn).

    accum_dtype (jnp.bfloat16 or the string "bfloat16") stores the
    grad-accumulation carry in that dtype — halves the per-microbatch
    grad-tree HBM traffic that bounds the grad-accum MFU ladder
    (dp._local_grads for the accuracy band; only meaningful with
    grad_accum > 1, ignored otherwise).

    state = {"params", "opt_state", "step"} — the same pytree-of-arrays
    state scheme as every other train step (checkpointable by
    train/checkpoint.py unchanged). Under a multi-device mesh, place the
    state replicated (or FSDP-sharded) and the batch data-sharded; jit
    inserts the psums (GSPMD).

    grad_accum > 1 accumulates per-micro-batch value_and_grad inside a
    lax.scan (parallel/dp.py _local_grads — the ONE accumulation
    implementation, shared with the CNN path): the backward runs
    micro-batch-by-micro-batch (no autodiff THROUGH the scan), so peak
    activation memory is one micro-batch's while the optimizer sees the
    exact full-batch mean gradient (equal micro-batches make the mean
    of means the batch mean; parity-tested — MoE's per-chunk routing
    statistics are the same estimator change as every microbatched
    trainer's). Must divide the batch.
    """
    import optax

    if accum_dtype is not None:
        accum_dtype = jnp.dtype(accum_dtype)
    impl = pick_attn_impl(attn_impl, seq_len or model.max_seq, compute_dtype)
    attn_fn = get_attn_fn(impl, mesh)
    loss = partial(
        lm_loss, model, attn_fn=attn_fn, compute_dtype=compute_dtype,
        remat=remat, moe_aux_weight=moe_aux_weight, ce_chunk=ce_chunk,
        moe_dispatch_chunk=moe_dispatch_chunk,
        moe_dispatch_dtype=moe_dispatch_dtype,
    )

    @partial(donate_jit, donate=donate)
    def step(state, tokens, targets):
        if grad_accum > 1 and tokens.shape[0] % grad_accum:
            raise ValueError(
                f"batch {tokens.shape[0]} not divisible by grad_accum "
                f"{grad_accum}"
            )
        # ONE accumulation implementation for both families: dp.py's
        # helper carries the interleaved micro-split (a contiguous split
        # would hand each micro-batch to a single device under GSPMD
        # batch sharding) and the scan that keeps one micro-batch of
        # activations live.
        from ..parallel.dp import local_grads_no_aux

        l, grads = local_grads_no_aux(
            loss, state["params"], tokens, targets, grad_accum,
            accum_dtype=accum_dtype,
        )
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        params = optax.apply_updates(state["params"], updates)
        return (
            {"params": params, "opt_state": opt_state,
             "step": state["step"] + 1},
            {"loss": l},
        )

    return step


def make_elastic_lm_train_step(
    model: TransformerLM,
    optimizer,
    mesh,
    *,
    elastic_width: int,
    attn_impl: str = "auto",
    seq_len: int | None = None,
    compute_dtype=None,
    remat: bool = False,
    donate: bool = True,
    moe_aux_weight: float = 0.01,
    ce_chunk: int = 0,
):
    """The LM train step with the width-invariant gradient reduction
    (parallel/elastic.py) — the elastic twin of make_lm_train_step.

    The plain LM step is a GSPMD jit: data parallelism falls out of the
    batch sharding, and XLA chooses how the batch reductions partition —
    which is exactly what changes bit patterns when the width changes.
    This step is an explicit shard_map over the 'data' axis instead, so
    the gradient is the canonical balanced-tree sum over fixed-size
    microbatches at every width: a run preempted at dp=4 and resumed at
    dp=2 stays on the uninterrupted run's bitwise trajectory (ISSUE 5;
    proven in tests/test_elastic.py). Pure-DP meshes only — the trainer
    rejects elastic_width on seq/model/pipe/expert meshes.
    """
    import optax
    from jax.sharding import PartitionSpec as P

    from ..parallel.elastic import elastic_grads
    from ..parallel.mesh import DATA_AXIS

    impl = pick_attn_impl(attn_impl, seq_len or model.max_seq, compute_dtype)
    attn_fn = get_attn_fn(impl)
    loss = partial(
        lm_loss, model, attn_fn=attn_fn, compute_dtype=compute_dtype,
        remat=remat, moe_aux_weight=moe_aux_weight, ce_chunk=ce_chunk,
    )
    n_data = mesh.shape.get(DATA_AXIS, 1)

    def step(state, tokens, targets):
        def grad_fn(px, py):
            l, grads = jax.value_and_grad(loss)(state["params"], px, py)
            return l, grads

        l, grads = elastic_grads(
            grad_fn, tokens, targets, elastic_width=elastic_width,
            axis=DATA_AXIS, axis_size=n_data,
        )
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        params = optax.apply_updates(state["params"], updates)
        return (
            {"params": params, "opt_state": opt_state,
             "step": state["step"] + 1},
            {"loss": l},
        )

    sharded = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return donate_jit(sharded, donate=donate), impl


def make_lm_state(model: TransformerLM, optimizer, seed: int = 0) -> dict:
    """Fresh {"params", "opt_state", "step"} for the LM train step."""
    params = model.init(jax.random.key(seed))
    return {
        "params": params,
        "opt_state": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def lm_flops_per_token(model: TransformerLM, seq_len: int) -> float:
    """Analytic forward+backward FLOPs per trained token (the MFU
    denominator; backward = 2x forward, the standard accounting).

    Per layer forward, per token: q proj 2d², kv proj 4·d·(Hkv·hd)
    (= 4d² for MHA, less under GQA), attn-out 2d², MLP 16d²·k where
    k = moe_top_k for MoE blocks (each routed token runs k experts of
    the same 4d hidden size; Switch k=1 matches dense, GShard k=2
    doubles the MLP work) plus the router 2·d·E, plus attention
    scores+values 2·s·d (causal: each query sees s/2 keys on average;
    QK^T and P·V each cost 2·(s/2)·d). Embedding head: 2·d·V.
    """
    d, s, v = model.dim, seq_len, model.vocab
    kv_dim = model.n_kv * model.head_dim
    k = model.moe_top_k if model.moe_experts else 1
    mlp = 16 * d * d * k
    gate = 2 * d * model.moe_experts if model.moe_experts else 0
    per_layer = (
        2 * d * d + 4 * d * kv_dim + 2 * d * d + mlp + gate + 2 * s * d
    )
    fwd = model.depth * per_layer + 2 * d * v
    return 3.0 * fwd


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
