"""Device-time benchmark for the attention paths (PERF.md methodology).

Times each implementation with `utils/sync.scan_two_point`: jitted
`lax.scan` windows of n and 2n calls, per-call time = (T(2n) − T(n)) / n
(any fixed per-window cost cancels), median of 3 samples.
The original single-window scan-of-3 harness smeared that fixed cost
across 3 iterations and overstated the s=8192 flash forward 8x (37.6 vs
4.6 ms) — the round-4 measurement correction in PERF.md. Prints one
line per implementation.

Usage: python scripts/bench_attention.py [--seq 32768] [--iters 10]
                                         [--dtype bfloat16] [--head-dim 128]
"""

from __future__ import annotations

import argparse
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mpi_cuda_cnn_tpu.utils.sync import grad_stacked
from mpi_cuda_cnn_tpu.utils.sync import scan_two_point as device_time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument("--iters", type=int, default=10,
                    help="n for the two-point (T(2n)-T(n))/n windows")
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--block", type=int, default=1024,
                    help="block size for the jnp blockwise path")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--bwd", action="store_true",
                    help="time fwd+bwd (gradients of sum(o^2) wrt "
                         "q, k, v) instead of the forward alone — the "
                         "PERF.md fused-backward table's command")
    args = ap.parse_args()

    from mpi_cuda_cnn_tpu.ops.attention import blockwise_attention
    from mpi_cuda_cnn_tpu.ops.pallas_attention import flash_attention
    from mpi_cuda_cnn_tpu.parallel.sp import make_ring_flash_attention

    b, s, h, d = 1, args.seq, args.heads, args.head_dim
    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), dt)
               for _ in range(3))
    n = args.iters
    tag = "fwd+bwd" if args.bwd else "causal "

    def measured(fn):
        """The forward itself, or fwd+bwd of sum(o²) via the shared
        grad_stacked wrapper (utils/sync.py)."""
        return grad_stacked(fn) if args.bwd else fn

    t = device_time(measured(partial(flash_attention, causal=True)),
                    n, q, k, v)
    print(f"flash_attention   {tag} s={s}: {t * 1000:8.1f} ms/call")

    # Ring-flash over however many devices are visible (p=1 on one chip:
    # the ring reduces to one diag fold — kernel cost + one merge).
    # Measured through the library's own wrapper so the benchmark and
    # the shipped program can't drift apart.
    devs = jax.devices()
    mesh = jax.sharding.Mesh(np.array(devs), ("seq",))
    ring = make_ring_flash_attention(mesh)
    t = device_time(measured(partial(ring, causal=True)), n, q, k, v)
    print(f"ring_flash (p={len(devs)}) {tag} s={s}: {t * 1000:8.1f} ms/call")

    t = device_time(
        measured(partial(blockwise_attention, block_size=args.block,
                         causal=True)),
        n, q, k, v,
    )
    print(f"jnp blockwise b{args.block} {tag} s={s}: {t * 1000:8.1f} ms/call")


if __name__ == "__main__":
    main()
