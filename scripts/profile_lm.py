"""Attribute the LM train-step wall-clock to its components.

VERDICT round 2: "32.4% MFU is good; the remaining 68% is unexplained."
This script explains it by ABLATION — each row times a program with one
component removed or swapped, all with the same two-point method as
scripts/bench_lm.py ((T2N - TN)/N cancels any fixed per-window cost),
completion forced by block_until_ready:

  full_step        fwd + bwd + AdamW update (the real train step)
  fwd_only         loss forward alone -> bwd+update = full - fwd
  fwd_identity_attn  forward with attention replaced by (q,k,v)->v
                     -> attention fwd share = fwd_only - this
  fwd_no_head      forward returning mean(features) (no head matmul, no
                     CE) -> head+CE share = fwd_only - this
  full_ce_chunked  the fused chunked-CE step (train/lm.lm_loss ce_chunk)
                     -> what the (B,S,V) f32 logits materialization costs

Differences of measurements, not a tracer: coarse (shares overlap where
XLA fuses across seams) but honest, and enough to rank where the next
milliseconds are. A jax.profiler trace dir can be captured alongside
(--profile-dir) for manual inspection in TensorBoard.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
from mpi_cuda_cnn_tpu.obs import cost as obs_cost
from mpi_cuda_cnn_tpu.train.lm import (
    get_attn_fn,
    lm_loss,
    make_lm_state,
    make_lm_train_step,
)
from mpi_cuda_cnn_tpu.train.optimizer import make_optimizer
from mpi_cuda_cnn_tpu.utils.backend import claim_device
from mpi_cuda_cnn_tpu.utils.sync import two_point


def _two_point(fn, steps):
    return two_point(fn, steps, warmup=2)


def _timed_loop(step_fn, state0, *args):
    def run(n):
        state = state0
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            state, out = step_fn(state, *args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    return run


def _timed_fwd(loss_fn, params, *args):
    def run(n):
        t0 = time.perf_counter()
        acc = None
        for _ in range(n):
            # Chain through the loss scalar so iterations are dependent
            # (XLA cannot elide or overlap them into one).
            out = loss_fn(params, *args) + (acc if acc is not None else 0.0)
            acc = out * 0.0
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    return run


def _accum_ablation(model, opt, state, tokens, targets, *, accum, cd,
                    attn_impl, attn_fn, steps):
    """Attribute the per-microbatch grad-accumulation overhead (the
    fitted ~8 ms/microbatch at the flagship, PERF.md) by ABLATION, the
    same differences-of-measurements method as the main rows:

      accum_full          the real accum step (scan + tree carry + AdamW)
      accum_no_update     same accumulation, optimizer update removed
                          -> update share = full - no_update
      accum_scalar_carry  scan runs every fwd+bwd but the carry holds
                          per-leaf SCALAR sums (backward cannot be
                          DCE'd; no grad-tree-extent add/read/write)
                          -> tree-carry share/microbatch =
                             (no_update - scalar_carry) / accum
      plain_no_update     one full-batch fwd+bwd, no scan, no update
                          -> scan/microbatching share/microbatch =
                             (scalar_carry - plain_no_update) / accum

    Coarse where XLA fuses across the seams (the carry add can ride the
    backward epilogue — then the tree-carry share reads ~0 and the floor
    is proven fused), but honest: every row is a measured program.
    """
    from mpi_cuda_cnn_tpu.parallel.dp import local_grads_no_aux
    from mpi_cuda_cnn_tpu.train.lm import lm_loss as _lm_loss

    def loss_fn(p, t, y):
        return _lm_loss(model, p, t, y, attn_fn=attn_fn, compute_dtype=cd)

    def split(t):
        a = accum
        return t.reshape(t.shape[0] // a, a, *t.shape[1:]).swapaxes(0, 1)

    @jax.jit
    def accum_no_update(state, tokens, targets):
        l, grads = local_grads_no_aux(
            loss_fn, state["params"], tokens, targets, accum
        )
        # Consume the grads at scalar extent so the accumulation isn't
        # dead code; the optimizer update is the only thing removed.
        return state, {"loss": l + 0.0 * sum(
            jnp.sum(g) for g in jax.tree.leaves(grads)
        )}

    @jax.jit
    def accum_scalar_carry(state, tokens, targets):
        xs, ys = split(tokens), split(targets)

        def body(c, xy):
            l, grads = jax.value_and_grad(loss_fn)(state["params"], *xy)
            s = sum(jnp.sum(g) for g in jax.tree.leaves(grads))
            return (c[0] + l, c[1] + s), None

        (l, s), _ = jax.lax.scan(
            body, (jnp.float32(0), jnp.float32(0)), (xs, ys)
        )
        return state, {"loss": l / accum + 0.0 * s}

    @jax.jit
    def plain_no_update(state, tokens, targets):
        l, grads = jax.value_and_grad(loss_fn)(
            state["params"], tokens, targets
        )
        return state, {"loss": l + 0.0 * sum(
            jnp.sum(g) for g in jax.tree.leaves(grads)
        )}

    from mpi_cuda_cnn_tpu.train.lm import make_lm_train_step

    accum_full = make_lm_train_step(
        model, opt, attn_impl=attn_impl, seq_len=tokens.shape[1],
        compute_dtype=cd, donate=False, grad_accum=accum,
    )

    rows = {}
    for name, fn in (
        ("accum_full", accum_full),
        ("accum_no_update", accum_no_update),
        ("accum_scalar_carry", accum_scalar_carry),
        ("plain_no_update", plain_no_update),
    ):
        rows[name] = _two_point(
            _timed_loop(fn, state, tokens, targets), steps
        )
    ms = {k: round(v * 1e3, 2) for k, v in rows.items()}
    a = accum
    derived = {
        "update_ms": round(ms["accum_full"] - ms["accum_no_update"], 2),
        "tree_carry_ms_per_microbatch": round(
            (ms["accum_no_update"] - ms["accum_scalar_carry"]) / a, 3
        ),
        "scan_overhead_ms_per_microbatch": round(
            (ms["accum_scalar_carry"] - ms["plain_no_update"]) / a, 3
        ),
    }
    costs = obs_cost.try_analyze(accum_full, state, tokens, targets)
    return ms, derived, costs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--attn", default="flash", choices=["flash", "oracle"])
    ap.add_argument("--ce-chunk", type=int, default=512)
    ap.add_argument("--grad-accum", type=int, default=0,
                    help="> 1: run the grad-accumulation overhead "
                         "ablation instead of the step-component rows "
                         "(attributes the per-microbatch cost to tree "
                         "carry vs scan machinery vs update)")
    ap.add_argument("--profile-dir", default=None,
                    help="also capture a jax.profiler trace of one step")
    ap.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    args = ap.parse_args()

    claim_device(args.device)  # utils/backend: DeviceError off-chip

    cd = jnp.bfloat16 if args.dtype == "bfloat16" else None
    model = TransformerLM(vocab=args.vocab, dim=args.dim, heads=args.heads,
                          depth=args.depth, max_seq=args.seq)
    opt = make_optimizer(3e-4, opt="adamw", schedule="constant")
    state = make_lm_state(model, opt, 0)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(
        rng.integers(0, model.vocab, (args.batch, args.seq + 1)), jnp.int32
    )
    tokens, targets = toks[:, :-1], toks[:, 1:]
    attn_fn = get_attn_fn(args.attn)

    if args.grad_accum > 1:
        if args.batch % args.grad_accum:
            raise SystemExit(
                f"--batch {args.batch} not divisible by --grad-accum "
                f"{args.grad_accum}"
            )
        ms, derived, costs = _accum_ablation(
            model, opt, state, tokens, targets, accum=args.grad_accum,
            cd=cd, attn_impl=args.attn, attn_fn=attn_fn, steps=args.steps,
        )
        print(json.dumps({
            "bench": "lm_accum_profile",
            "model": f"d{args.dim}x{args.depth} h{args.heads} "
                     f"s{args.seq} v{args.vocab} b{args.batch} "
                     f"{args.dtype}+{args.attn} accum{args.grad_accum}",
            **ms, **derived,
            "flops_per_step": costs.flops if costs else None,
            "bytes_per_step": costs.bytes_accessed if costs else None,
            "aliased_outputs": costs.aliased_outputs if costs else None,
            "alias_bytes": costs.alias_bytes if costs else None,
            "backend": jax.default_backend(),
        }))
        return

    rows = {}

    # full train step (fwd+bwd+update), dense CE — the bench_lm headline.
    step = make_lm_train_step(model, opt, attn_impl=args.attn,
                              seq_len=args.seq, compute_dtype=cd,
                              donate=False)
    rows["full_step"] = _two_point(_timed_loop(step, state, tokens, targets),
                                   args.steps)

    # fused chunked-CE step.
    step_cc = make_lm_train_step(model, opt, attn_impl=args.attn,
                                 seq_len=args.seq, compute_dtype=cd,
                                 donate=False, ce_chunk=args.ce_chunk)
    rows["full_ce_chunked"] = _two_point(
        _timed_loop(step_cc, state, tokens, targets), args.steps
    )

    # forward-only ablations.
    def fwd(attn, no_head):
        if no_head:
            def f(p, t, y):
                feats = model.apply(p, t, attn_fn=attn, compute_dtype=cd,
                                    return_features=True)
                return jnp.mean(feats.astype(jnp.float32))
        else:
            def f(p, t, y):
                return lm_loss(model, p, t, y, attn_fn=attn,
                               compute_dtype=cd)
        return jax.jit(f)

    rows["fwd_only"] = _two_point(
        _timed_fwd(fwd(attn_fn, False), state["params"], tokens, targets),
        args.steps,
    )
    rows["fwd_identity_attn"] = _two_point(
        _timed_fwd(fwd(lambda q, k, v: v, False), state["params"],
                   tokens, targets),
        args.steps,
    )
    rows["fwd_no_head"] = _two_point(
        _timed_fwd(fwd(attn_fn, True), state["params"], tokens, targets),
        args.steps,
    )

    if args.profile_dir:
        with jax.profiler.trace(args.profile_dir):
            jax.block_until_ready(step(state, tokens, targets)[1])

    ms = {k: round(v * 1e3, 2) for k, v in rows.items()}
    derived = {
        "bwd_update_ms": round(ms["full_step"] - ms["fwd_only"], 2),
        "attn_fwd_ms": round(ms["fwd_only"] - ms["fwd_identity_attn"], 2),
        "head_ce_fwd_ms": round(ms["fwd_only"] - ms["fwd_no_head"], 2),
        "ce_chunk_delta_ms": round(
            ms["full_ce_chunked"] - ms["full_step"], 2
        ),
    }
    tokens_per_step = args.batch * args.seq
    # FLOPs of the COMPILED full step (obs/cost.py XLA cost analysis),
    # not an analytic formula — the number matches the program the rows
    # above timed, byte-accounting included.
    costs = obs_cost.try_analyze(step, state, tokens, targets)
    print(json.dumps({
        "bench": "lm_profile",
        "model": f"d{args.dim}x{args.depth} h{args.heads} s{args.seq} "
                 f"v{args.vocab} b{args.batch} {args.dtype}+{args.attn}",
        **ms, **derived,
        "tokens_per_s": round(tokens_per_step / rows["full_step"]),
        "flops_per_step": costs.flops if costs else None,
        "bytes_per_step": costs.bytes_accessed if costs else None,
        "collectives": costs.collectives if costs else None,
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    main()
