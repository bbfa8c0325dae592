"""On-chip compile + parity + perf check for the GQA flash kernels.

VERDICT round 2 item 2: the 5-D (b, hkv, group, qblock, kblock) grid
restructure of ops/pallas_attention.py landed after the round-2 backend
outage and has "never compiled on real hardware" — the reference's own
cautionary tale (CUDAcnn.cu:167, committed but never built). This script
closes that hole the moment a chip is reachable:

for each (s, kv_heads) in the matrix it
  1. compiles + runs the fused flash forward on the real backend,
  2. checks parity against the jnp oracle (f32, rtol 2e-2 for bf16),
  3. times fwd and fwd+bwd with the two-point method,
printing one JSON line per config and a final summary line. Any compile
failure or parity miss makes the process exit nonzero — this is a check,
not just a bench.
"""

from __future__ import annotations

import argparse
import json
import sys

import os

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mpi_cuda_cnn_tpu.ops.attention import (
    attention,
    blockwise_attention,
    repeat_kv,
)
from mpi_cuda_cnn_tpu.ops.pallas_attention import flash_attention
from mpi_cuda_cnn_tpu.utils.sync import (
    grad_stacked,
    scan_two_point,
)


def check_config(*, b, h, hkv, s, d, dtype, bwd, rng):
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)

    # c is a zero scalar threaded through iterations purely as a data
    # dependency (q + c is numerically q).
    fwd = jax.jit(
        lambda q, k, v, c: flash_attention(q + c, k, v, True)
    )
    zero = jnp.zeros((), dtype)
    out = jax.block_until_ready(fwd(q, k, v, zero))  # the compile that must not fail

    # Parity vs the oracle (repeat_kv handles GQA). The quadratic oracle
    # materializes an O(S^2) score tensor — ~2 GB at s=8192 — so large s
    # uses the bounded-memory blockwise oracle (exact same math, online
    # softmax) to keep a reference OOM from masquerading as a kernel
    # failure.
    if s <= 4096:
        want = attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32), causal=True)
    else:
        want = blockwise_attention(
            q.astype(jnp.float32),
            repeat_kv(k.astype(jnp.float32), h),
            repeat_kv(v.astype(jnp.float32), h),
            block_size=1024, causal=True,
        )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
    ref = float(jnp.max(jnp.abs(want))) or 1.0
    rel = err / ref
    ok = rel < tol

    # Timing via the shared on-device-scan recipe (host-dispatch chains
    # did not resolve these sub-10 ms kernels on the earlier
    # installation — negative columns at n=3 AND n=25); the fwd+bwd
    # target is the shared grad_stacked wrapper.
    def timed(fn, n, *args):
        t = scan_two_point(fn, n, *args)
        if t * n < 0.05:
            # The s=2048 kernels are ~0.1 ms: n=25 gives ~2.5 ms of
            # window signal, below the window-to-window jitter — the
            # source of the 2026-07-31 captures' occasional negative
            # columns.
            # Re-measure with enough iterations for ~100 ms of signal.
            # A non-positive first read says nothing about the kernel's
            # real cost, so grow boundedly (10x) rather than jumping to
            # the iteration cap — at a ~5 ms kernel the cap would mean
            # ~90 s for one cell and blow the capture step's timeout.
            n2 = 10 * n if t <= 0 else min(max(50, int(0.1 / t)), 2000)
            t = scan_two_point(fn, n2, *args)
        return t

    fwd_fn = lambda q, k, v: flash_attention(q, k, v, True)
    t_fwd = timed(fwd_fn, 25, q, k, v)
    t_bwd = None
    if bwd:
        t_bwd = timed(grad_stacked(fwd_fn), 10, q, k, v)
    return {
        "s": s, "kv_heads": hkv, "dtype": str(jnp.dtype(dtype)),
        "parity_rel_err": round(rel, 6), "parity_ok": ok,
        "fwd_ms": round(t_fwd * 1e3, 2),
        "fwd_bwd_ms": round(t_bwd * 1e3, 2) if t_bwd is not None else None,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--seqs", default="2048,8192")
    ap.add_argument("--kv-heads", default="8,2,1",
                    help="matrix of kv head counts (heads = MHA)")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32"])
    ap.add_argument("--no-bwd", action="store_true")
    args = ap.parse_args()

    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    rows, failed = [], 0
    for s in (int(x) for x in args.seqs.split(",")):
        for hkv in (int(x) for x in args.kv_heads.split(",")):
            try:
                r = check_config(
                    b=args.batch, h=args.heads, hkv=hkv, s=s,
                    d=args.head_dim, dtype=dtype, bwd=not args.no_bwd,
                    rng=rng,
                )
            except Exception as exc:  # noqa: BLE001 — a compile failure IS the finding
                r = {"s": s, "kv_heads": hkv, "error": repr(exc)[:400],
                     "parity_ok": False}
            failed += not r.get("parity_ok", False)
            rows.append(r)
            print(json.dumps({"bench": "gqa_flash_check", **r}), flush=True)

    print(json.dumps({
        "metric": "gqa_flash_check",
        "configs": len(rows),
        "failed": failed,
        "backend": jax.default_backend(),
    }))
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
