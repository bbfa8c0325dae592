"""Attribute the single-chip MoE step's milliseconds (VERDICT r4 item 4).

The round-4 measurement: the d512x8 MoE LM step (E=8, top-2, b=8,
s=2048) runs at 235 ms / 11.6% MFU vs the dense twin's 53 ms / 33.8% —
a 6x efficiency cliff explained only by a paragraph. This script turns
the paragraph into numbers, by timing the moe_mlp body's components in
isolation (shared scan_two_point recipe) and the full step under
ablations.

The hypothesis the micro rows test: the dense one-hot dispatch/combine
einsums are QUADRATIC in tokens. dispatch is (T, E, C) with
C = ceil(T*k*cf/E), so the "tec,td->ecd" contraction costs
2*(E*C)*T*D ~ 2*k*cf*T^2*D FLOPs — at T = b*s = 16384 that is ~0.7
TFLOP per MoE layer per direction, several times the expert FFN's
useful work. Under EP over a P-device mesh each shard dispatches its
LOCAL T/P tokens (the cost falls P^2), which is why the design point is
fine and ONE chip is the pathology. The fix measured alongside:
`dispatch_chunk` (parallel/ep.py) — route in fixed-size token chunks,
making the term linear in T while staying pure MXU einsums.

One JSON line per row + a summary attribution line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mpi_cuda_cnn_tpu.obs import cost as obs_cost
from mpi_cuda_cnn_tpu.parallel.ep import (
    _expert_ffn,
    init_moe_params,
    moe_mlp,
    topk_dispatch,
)
from mpi_cuda_cnn_tpu.utils.backend import claim_device
from mpi_cuda_cnn_tpu.utils.sync import scan_two_point


def _cap(t: int, k: int, cf: float, e: int) -> int:
    return max(1, -int(-t * k * cf // e))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--tokens", type=int, default=16384,
                    help="T = batch*seq of the round-4 MoE bench row")
    ap.add_argument("--experts", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=2)
    ap.add_argument("--cf", type=float, default=1.25)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--chunks", default="0,2048,4096",
                    help="dispatch_chunk values to measure (0 = off)")
    ap.add_argument("--sweep", action="store_true",
                    help="also run the E x cf full-body sweep")
    ap.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    args = ap.parse_args()

    claim_device(args.device)  # utils/backend: DeviceError off-chip

    t, d, e, k = args.tokens, args.dim, args.experts, args.top_k
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32).astype(dt)
    params = init_moe_params(jax.random.key(0), d, args.hidden, e)
    cap = _cap(t, k, args.cf, e)

    def emit(row):
        print(json.dumps(row), flush=True)

    # --- micro rows: each pipeline component in isolation -------------
    # router+dispatch-build: gating softmax, top-k, cumsum position
    # masking, the (T, E, C) one-hot assembly (VPU work, no big matmul).
    def build(xx):
        disp, comb, aux = topk_dispatch(xx, params["gate"], e, cap, k)
        return disp[:, 0, :] + comb[:, 0, :] + aux

    ms_build = scan_two_point(build, args.iters, x) * 1e3

    # The (T, E, C) routing tensors and expert stacks are passed as
    # ARGUMENTS, never closed over: a closure constant is baked into the
    # jitted program body, and at T=16k the dispatch tensor alone is
    # 2.7 GB of constants in the compiled program.
    disp, comb, _ = topk_dispatch(x, params["gate"], e, cap, k)
    disp = disp.astype(dt)
    comb = comb.astype(dt)
    w1c = params["w1"].astype(dt)
    w2c = params["w2"].astype(dt)

    # dispatch einsum: (T,E,C) x (T,D) -> (E,C,D) — the suspected
    # quadratic term (2*E*C*T*D FLOPs).
    ms_disp = scan_two_point(
        lambda xx, dd: jnp.einsum("tec,td->ecd", dd, xx), args.iters,
        x, disp,
    ) * 1e3

    expert_in = jnp.einsum("tec,td->ecd", disp, x)

    # expert FFN: the USEFUL MoE compute (2 batched GEMMs over E*C slots).
    ms_ffn = scan_two_point(
        lambda h, w1, w2: _expert_ffn(h, w1, w2),
        args.iters, expert_in, w1c, w2c,
    ) * 1e3

    expert_out = _expert_ffn(expert_in, w1c, w2c)

    # combine einsum: (T,E,C) x (E,C,D) -> (T,D) — the quadratic twin.
    ms_comb = scan_two_point(
        lambda ee, cc: jnp.einsum("tec,ecd->td", cc, ee), args.iters,
        expert_out, comb,
    ) * 1e3

    # GFLOPs of each timed component from XLA cost analysis of the SAME
    # jitted program (obs/cost.py) — the hypothesis's 2*E*C*T*D algebra
    # is now checked against the compiler's count instead of asserted.
    def _gflop(fn, *a):
        c = obs_cost.try_analyze(jax.jit(fn), *a)
        return round(c.flops / 1e9, 1) if c and c.flops else None

    flops = {
        "dispatch_gflop": _gflop(
            lambda xx, dd: jnp.einsum("tec,td->ecd", dd, xx), x, disp
        ),
        "ffn_gflop": _gflop(_expert_ffn, expert_in, w1c, w2c),
        "combine_gflop": _gflop(
            lambda ee, cc: jnp.einsum("tec,ecd->td", cc, ee),
            expert_out, comb,
        ),
    }
    emit({
        "bench": "moe_profile", "T": t, "E": e, "top_k": k, "cf": args.cf,
        "capacity": cap, "dtype": args.dtype,
        "router_dispatch_build_ms": round(ms_build, 3),
        "dispatch_einsum_ms": round(ms_disp, 3),
        "expert_ffn_ms": round(ms_ffn, 3),
        "combine_einsum_ms": round(ms_comb, 3),
        **flops,
        "backend": jax.default_backend(),
    })

    # --- full moe_mlp body at each dispatch_chunk ---------------------
    gate = params["gate"]  # (D, E) — small enough to close over
    for chunk in (int(c) for c in args.chunks.split(",")):
        kw = {"n_experts": e, "capacity_factor": args.cf, "axis": None,
              "top_k": k}
        if chunk:
            kw["dispatch_chunk"] = chunk

        def body(xx, w1, w2, kw=kw):
            y, aux = moe_mlp(xx, {"gate": gate, "w1": w1, "w2": w2}, **kw)
            return y + aux

        # Expert stacks in the COMPUTE dtype, like the micro rows and
        # the scatter prototype — one dtype across every compared row.
        ms_body = scan_two_point(body, args.iters, x, w1c, w2c) * 1e3
        emit({
            "bench": "moe_profile_body", "dispatch_chunk": chunk,
            "T": t, "E": e, "top_k": k, "cf": args.cf,
            "moe_mlp_ms": round(ms_body, 3),
            "backend": jax.default_backend(),
        })

    # --- scatter-dispatch prototype (round-5 experiment) --------------
    # The dense formulation's quadratic terms come from the (T, E, C)
    # routing tensors; a scatter/gather formulation has none: tokens
    # scatter-add into their (expert, slot) rows (one trash row absorbs
    # drops), experts run the same batched GEMMs, outputs gather back.
    # O(T*D) data movement — but XLA lowers scatter on TPU via sort
    # machinery, so whether it BEATS the chunked einsums is an
    # empirical question this row answers.
    def scatter_body(xx, w1, w2, g=params["gate"], e=e, cap=cap, k=k):
        t_, d_ = xx.shape
        probs = jax.nn.softmax((xx @ g).astype(jnp.float32), axis=-1)
        vals, idx = jax.lax.top_k(probs, k)
        gates = vals if k == 1 else vals / jnp.sum(vals, -1, keepdims=True)
        used = jnp.zeros((e,), jnp.float32)
        slots, gsel = [], []
        for j in range(k):
            onehot = jax.nn.one_hot(idx[:, j], e, dtype=jnp.float32)
            pos = jnp.cumsum(onehot, 0) - 1.0 + used[None, :]
            pos_j = jnp.take_along_axis(
                pos, idx[:, j : j + 1], 1
            )[:, 0].astype(jnp.int32)
            keep = pos_j < cap
            slots.append(jnp.where(keep, idx[:, j] * cap + pos_j,
                                   e * cap))
            gsel.append(jnp.where(keep, gates[:, j], 0.0))
            used = used + jnp.sum(onehot * (pos < cap), axis=0)
        expert_in = jnp.zeros((e * cap + 1, d_), xx.dtype)
        for slot in slots:
            expert_in = expert_in.at[slot].add(xx)
        out = _expert_ffn(
            expert_in[: e * cap].reshape(e, cap, d_), w1, w2
        ).reshape(e * cap, d_)
        out = jnp.concatenate(
            [out, jnp.zeros((1, d_), out.dtype)], axis=0
        )
        y = sum(
            gs[:, None].astype(out.dtype) * out[slot]
            for gs, slot in zip(gsel, slots)
        )
        return y

    ms_scatter = scan_two_point(
        scatter_body, args.iters, x, params["w1"].astype(dt),
        params["w2"].astype(dt),
    ) * 1e3
    emit({
        "bench": "moe_profile_scatter", "T": t, "E": e, "top_k": k,
        "cf": args.cf, "moe_scatter_ms": round(ms_scatter, 3),
        "backend": jax.default_backend(),
    })

    # --- E x cf sweep (fixed total params: E experts of hidden H) -----
    if args.sweep:
        for ee in (4, 8):
            p_e = init_moe_params(jax.random.key(0), d, args.hidden, ee)
            for cf in (1.0, 1.25, 2.0):
                def body(xx, w1, w2, g=p_e["gate"], ee=ee, cf=cf):
                    y, aux = moe_mlp(xx, {"gate": g, "w1": w1, "w2": w2},
                                     n_experts=ee, capacity_factor=cf,
                                     axis=None, top_k=k)
                    return y + aux

                ms_body = scan_two_point(
                    body, args.iters, x, p_e["w1"].astype(dt),
                    p_e["w2"].astype(dt),
                ) * 1e3
                emit({
                    "bench": "moe_profile_sweep", "E": ee, "cf": cf,
                    "top_k": k, "T": t,
                    "moe_mlp_ms": round(ms_body, 3),
                    "capacity": _cap(t, k, cf, ee),
                    "backend": jax.default_backend(),
                })


if __name__ == "__main__":
    main()
