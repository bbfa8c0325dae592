"""Per-op conv benchmark: XLA emitter vs the Pallas direct kernels.

Produces the per-shape table in PERF.md ("Pallas conv/dense kernels:
per-shape analysis"). Timing = `utils/sync.scan_two_point` (the shared
two-point on-device-scan recipe: (T(2N) - T(N)) / N over jitted scans,
median of 3 — any fixed per-window cost cancels instead of needing to
be amortized).

    python scripts/bench_conv_shapes.py [--iters 200]
"""

from __future__ import annotations

import argparse
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np

from mpi_cuda_cnn_tpu.ops.conv import conv2d
from mpi_cuda_cnn_tpu.ops.pallas_conv_gemm import conv2d_pallas_gemm
from mpi_cuda_cnn_tpu.ops.pallas_ops import conv2d_pallas
from mpi_cuda_cnn_tpu.utils.sync import scan_two_point

# The round-1 verdict's question shapes: cifar3conv/vgg_small layers +
# the reference's own conv1.
SHAPES = [
    (128, 32, 32, 3, 3, 64, 1, 1),
    (128, 32, 32, 64, 3, 64, 1, 1),
    (128, 16, 16, 64, 3, 128, 1, 1),
    (128, 8, 8, 128, 3, 256, 1, 1),
    (32, 28, 28, 1, 3, 16, 2, 1),
]


def dev_time(fn, x, w, iters, reps=3):
    """Per-op ms via the shared two-point scan recipe
    (utils/sync.scan_two_point): (T(2N) - T(N)) / N over jitted
    on-device scans, median of `reps` — any fixed per-window dispatch
    cost (which would otherwise compress every ratio toward 1.0)
    cancels, and sub-10% differences are not resolvable from one
    sample."""
    return scan_two_point(fn, iters, x, w, reps=reps) * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=200)
    args = ap.parse_args()
    rng = np.random.default_rng(0)
    for dt_name, cast in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
        for (n, h, w, ci, k, co, s, p) in SHAPES:
            x = jnp.asarray(rng.standard_normal((n, h, w, ci)), cast)
            wt = jnp.asarray(rng.standard_normal((k, k, ci, co)), cast)
            t_xla = dev_time(partial(conv2d, stride=s, padding=p), x, wt,
                             args.iters)
            t_pl = dev_time(partial(conv2d_pallas, stride=s, padding=p), x,
                            wt, args.iters)
            # Implicit-GEMM formulation (stride-1 only): the round-5
            # answer to "was the direct kernel's deep-shape loss
            # structural or a formulation gap?"
            t_gemm = (
                dev_time(partial(conv2d_pallas_gemm, stride=s, padding=p),
                         x, wt, args.iters)
                if s == 1 else float("nan")
            )
            print(
                f"{dt_name} {n}x{h}x{w}x{ci} k{k} -> {co} s{s}: "
                f"xla {t_xla:7.3f} ms  pallas {t_pl:7.3f} ms  "
                f"gemm {t_gemm:7.3f} ms  "
                f"ratio {t_pl / t_xla:5.2f}/{t_gemm / t_xla:5.2f}"
            )


if __name__ == "__main__":
    main()
