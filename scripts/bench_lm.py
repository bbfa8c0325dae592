"""MFU-honest transformer-LM pretraining benchmark.

The CNN epoch benchmark (bench.py) is dispatch/VPU-bound at the
reference's 361k-param model and cannot show the MXU being fed; this
bench does: a ~34M-param decoder-only LM (d=512, 8 layers, 8 heads,
s=2048, vocab 8192) trained with AdamW on the real train step
(train/lm.py), measuring tokens/s and model FLOPs utilization against
the chip's peak.

Runs the matrix {f32, bf16} x {oracle, flash} by default (--quick runs
bf16+flash only) and prints one JSON line per config plus a summary
line. Two FLOPs accountings per row, both computed (obs/cost.py — no
hand-typed constants): `mfu` uses the analytic model FLOPs
(lm_flops_per_token — the standard MFU numerator: remat must not
inflate utilization), `mfu_xla` uses XLA cost analysis of the compiled
step (the FLOPs actually executed). Peaks come from the one registry
(obs.cost.PEAK_TFLOPS); --peak-tflops overrides for other chips.
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
    count_params,
    lm_flops_per_token,
    make_lm_state,
    make_lm_train_step,
)
from mpi_cuda_cnn_tpu.train.optimizer import make_optimizer
from mpi_cuda_cnn_tpu.utils.backend import claim_device
from mpi_cuda_cnn_tpu.utils.sync import two_point


def bench_config(model, *, batch, seq, compute_dtype, attn_impl,
                 steps=20, warmup=3, seed=0, ce_chunk=0,
                 moe_dispatch_chunk=0, grad_accum=1, remat=False,
                 accum_dtype=None):
    opt = make_optimizer(3e-4, opt="adamw", schedule="constant")
    step_fn = make_lm_train_step(
        model, opt, attn_impl=attn_impl, seq_len=seq,
        compute_dtype=compute_dtype, remat=remat, ce_chunk=ce_chunk,
        moe_dispatch_chunk=moe_dispatch_chunk, grad_accum=grad_accum,
        accum_dtype=accum_dtype,
    )
    state = make_lm_state(model, opt, seed)
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(
        rng.integers(0, model.vocab, (batch, seq + 1)), jnp.int32
    )
    tokens, targets = toks[:, :-1], toks[:, 1:]

    # Completion is forced by fetching the final loss, which the
    # caller wants anyway: it depends on the whole step chain, so one
    # fetch drains it all.
    def run(state, n):
        t0 = time.perf_counter()
        m = None
        for _ in range(n):
            state, m = step_fn(state, tokens, targets)
        loss = float(m["loss"])
        return state, time.perf_counter() - t0, loss

    for _ in range(warmup):
        state, m = step_fn(state, tokens, targets)
    float(m["loss"])

    # Shared two-point core (utils/sync.two_point): (T2N - TN)/N cancels
    # any fixed per-window cost, median-of-3 absorbs a stray slow window
    # (observed 2026-07-31: one s=8192 sample pair read 15x slow, the
    # re-run was normal). warmup=0 — warmed above.
    box = {"state": state, "loss": None}

    def timed(k):
        box["state"], dt, box["loss"] = run(box["state"], k)
        return dt

    dt = two_point(timed, steps, warmup=0)
    # Compiled-step accounting (obs/cost.py): the FLOPs XLA actually
    # executes for THIS program — the mfu_xla numerator.
    costs = obs_cost.try_analyze(step_fn, box["state"], tokens, targets)
    return dt, box["loss"], costs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="0 = MHA; < heads = GQA (flash kernel zero-copy)")
    ap.add_argument("--pos", type=str, default="learned",
                    help="learned | rope")
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="0 = dense MLP; >0 = Switch/GShard MoE blocks")
    ap.add_argument("--moe-top-k", type=int, default=1,
                    help="experts per token (1 = Switch, 2 = GShard); "
                         "lm_flops_per_token scales the MLP term by k")
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="bf16 peak of the chip (MFU denominator); f32 "
                         "configs use it scaled by the v5e f32/bf16 ratio. "
                         "Default: v5e (197, f32 49)")
    ap.add_argument("--quick", action="store_true",
                    help="bf16+flash only (the headline config)")
    ap.add_argument("--ce-chunk", type=int, default=0,
                    help="chunked fused cross-entropy (train/lm.lm_loss): "
                         "S-chunk size, 0 = dense (B,S,V) logits")
    ap.add_argument("--moe-dispatch-chunk", type=int, default=0,
                    help="chunked MoE routing (ep.moe_mlp): token-chunk "
                         "size, 0 = whole-batch dispatch. Single-chip "
                         "lever for the quadratic dispatch einsum")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="micro-batch accumulation (must divide batch); "
                         "amortizes the optimizer update's HBM traffic")
    ap.add_argument("--accum-dtype", default=None,
                    choices=["bfloat16", "float32"],
                    help="grad-accumulation carry dtype (default: the "
                         "param dtype, f32 — exact); measured a TIE "
                         "on v5e (XLA fuses the accumulate into the bwd "
                         "epilogue — PERF.md) but kept for backends "
                         "where it isn't (~1-2%% grad error band)")
    ap.add_argument("--remat", action="store_true",
                    help="jax.checkpoint per block (recompute-in-bwd)")
    ap.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    args = ap.parse_args()

    # "float32" == the default exact carry: normalize to None so the
    # accumulation path never does a silent f32->f32 cast round-trip
    # (ADVICE.md — the old choices list also made None unreachable).
    if args.accum_dtype == "float32":
        args.accum_dtype = None

    claim_device(args.device)  # utils/backend: DeviceError off-chip

    model = TransformerLM(
        vocab=args.vocab, dim=args.dim, heads=args.heads,
        depth=args.depth, max_seq=args.seq, kv_heads=args.kv_heads,
        pos=args.pos, moe_experts=args.moe_experts,
        moe_top_k=args.moe_top_k,
    )

    def peak_for(dtype_name):
        """MFU denominator (TFLOP/s) per compute dtype — the ONE peak
        formula, obs.cost.peak_flops: f32 matmuls have their own (4x
        lower) MXU peak, a --peak-tflops override names the chip's bf16
        peak and f32 scales by the same ratio as v5e."""
        peak = obs_cost.peak_flops(
            dtype_name, override_tflops=args.peak_tflops
        )
        return peak / 1e12 if peak else None

    tokens_per_step = args.batch * args.seq
    flops_per_step = lm_flops_per_token(model, args.seq) * tokens_per_step

    # MFU is only meaningful against a real chip peak: emit it when the
    # backend is a TPU or the caller supplied --peak-tflops; otherwise
    # report tokens/s with mfu=null rather than an MFU against a peak the
    # backend doesn't have.
    backend = jax.default_backend()
    mfu_valid = backend == "tpu" or args.peak_tflops is not None

    # (dtype, attn, ce_chunk) rows. The default matrix ends with the
    # fused chunked-CE variant of the headline config so the dense-vs-
    # chunked comparison is measured in the same run; --ce-chunk applies
    # its value to EVERY row instead.
    if args.quick:
        configs = [("bfloat16", "flash", args.ce_chunk)]
    elif args.ce_chunk:
        configs = [
            ("float32", "oracle", args.ce_chunk),
            ("float32", "flash", args.ce_chunk),
            ("bfloat16", "oracle", args.ce_chunk),
            ("bfloat16", "flash", args.ce_chunk),
        ]
    else:
        ce_default = 512 if args.seq % 512 == 0 else args.seq
        configs = [
            ("float32", "oracle", 0), ("float32", "flash", 0),
            ("bfloat16", "oracle", 0), ("bfloat16", "flash", 0),
            ("bfloat16", "flash", ce_default),
        ]

    results = {}
    nparams = count_params(model.init(jax.random.key(0)))
    for dtype_name, impl, ce in configs:
        cd = jnp.bfloat16 if dtype_name == "bfloat16" else None
        dt, loss, costs = bench_config(
            model, batch=args.batch, seq=args.seq,
            compute_dtype=cd, attn_impl=impl, steps=args.steps,
            ce_chunk=ce, moe_dispatch_chunk=args.moe_dispatch_chunk,
            grad_accum=args.grad_accum, remat=args.remat,
            accum_dtype=args.accum_dtype,
        )
        tok_s = tokens_per_step / dt
        mfu = (
            round(flops_per_step / dt / (peak_for(dtype_name) * 1e12), 4)
            if mfu_valid else None
        )
        xla_flops = costs.flops if costs else None
        mfu_xla = (
            round(xla_flops / dt / (peak_for(dtype_name) * 1e12), 4)
            if mfu_valid and xla_flops else None
        )
        key = f"{dtype_name}+{impl}" + (f"+ce{ce}" if ce else "")
        results[key] = {
            "step_ms": round(dt * 1e3, 2),
            "tokens_per_s": round(tok_s),
            "mfu": mfu,
            "mfu_xla": mfu_xla,
            "xla_flops_per_step": xla_flops,
            "collectives": costs.collectives if costs else None,
            "loss": round(loss, 4),
        }
        extras = {}
        if args.moe_dispatch_chunk:
            extras["moe_dispatch_chunk"] = args.moe_dispatch_chunk
        if args.grad_accum > 1:
            extras["grad_accum"] = args.grad_accum
        if args.accum_dtype:
            extras["accum_dtype"] = args.accum_dtype
        if args.remat:
            extras["remat"] = True
        print(json.dumps({
            "bench": "lm_pretrain", "dtype": dtype_name, "attn": impl,
            "ce_chunk": ce, **extras, **results[key],
        }))

    best = max(results.items(), key=lambda kv: kv[1]["tokens_per_s"])
    print(json.dumps({
        "metric": "lm_tokens_per_s",
        "value": best[1]["tokens_per_s"],
        "unit": "tokens/s",
        "config": best[0],
        "mfu": best[1]["mfu"],
        "params": nparams,
        "model": f"d{args.dim}x{args.depth} h{args.heads} "
                 f"s{args.seq} v{args.vocab} b{args.batch}"
                 + (f" moe{args.moe_experts}k{args.moe_top_k}"
                    if args.moe_experts else ""),
        "peak_tflops": peak_for(best[0].split("+")[0]) if mfu_valid else None,
        "backend": backend,
    }))


if __name__ == "__main__":
    main()
