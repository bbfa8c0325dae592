"""Implicit-GEMM (im2col-in-VMEM) Pallas conv — the deep-shape formulation.

The direct kernel (pallas_ops.py `_conv1_kernel`, the TPU twin of
CUDAcnn.cu:167-195) loses to XLA's conv emitter at every measured shape
(PERF.md per-shape table). At the DEEP shapes (Cin >= 64) the mechanism
is lane waste: it issues kh*kw separate MXU contractions with K = Cin,
and Cin = 64 fills half of the MXU's 128 contraction lanes. This module
tries the standard fix the round-4 verdict asked for: build the im2col
patch tile IN VMEM (never in HBM — materialized patches would cost
kh*kw times the input's HBM traffic, which is why the XLA-side im2col
was never the answer) and feed the MXU ONE (BN*OH*OW, kh*kw*Cin)
contraction per tile:

    out = P @ W_flat,  P[:, (ky*kw+kx)*Cin : +Cin] = window(ky, kx)

At Cin=64, K grows 64 -> 576: ~90% lane utilization over the direct
kernel's 50%, and one accumulator pass instead of nine.

The window slices are the same VPU relayouts the direct kernel performs;
the change is purely how the MXU consumes them (concatenated once vs
nine half-filled dots). Stride-1 only — the deep VGG/CIFAR shapes where
the gap lives are all k3/s1/p1; strided convs keep the space-to-batch
direct path (pallas_ops._conv_forward). Backward reuses pallas_ops'
existing kernels (dx transposed-conv, dw accumulator) unchanged.

Measured verdict lives in PERF.md ("Pallas conv/dense kernels" section);
`scripts/bench_conv_shapes.py` emits the three-way comparison rows
(XLA / direct / gemm) unconditionally.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.backend import pallas_interpret
from .pallas_ops import _conv_bwd, _flatten_pixels


def _conv1_gemm_kernel(x_ref, w_ref, o_ref, *, kh, kw, oh, ow):
    """One batch tile of stride-1 valid conv as ONE MXU contraction.

    x_ref: (BN, Hp, Wp, Cin) VMEM block, Hp >= oh+kh-1, Wp >= ow+kw-1.
    w_ref: (kh*kw*Cin, Cout) — the kernel pre-flattened in patch order.
    o_ref: (BN, OH, OW, Cout).

    All kh*kw window slices are static (small k: the VMEM budget picker
    accounts for every live slice), concatenated on the lane dim into
    the patch tile P, then a single dot. The concat is a lane-dim
    relayout — the same per-offset copies the direct kernel performs —
    but the contraction runs once at K = kh*kw*Cin instead of kh*kw
    times at K = Cin.
    """
    bn = x_ref.shape[0]
    cin = x_ref.shape[3]
    m = bn * oh * ow
    slices = [
        x_ref[:, ky : ky + oh, kx : kx + ow, :]
        for ky in range(kh)
        for kx in range(kw)
    ]
    if x_ref.dtype == jnp.float32:
        # Concatenate the window slices as 4-D values FIRST, then one
        # pixel flatten — measurably faster (this ordering is what puts
        # the deep f32 shapes AT or past XLA, PERF.md round-5 table).
        p4 = jnp.concatenate(slices, axis=-1)  # (BN, OH, OW, kh*kw*Cin)
        p = p4.reshape(m, kh * kw * cin)
    else:
        # Packed dtypes: Mosaic rejects the 4-D lane concat ("offset
        # mismatch on non-concat dimension"), so flatten each slice
        # (f32 round-trip) and concat in 2-D.
        p = jnp.concatenate(
            [_flatten_pixels(s, m, cin) for s in slices], axis=-1
        )                                               # (M, kh*kw*Cin)
    o_ref[:] = (
        jnp.dot(p, w_ref[:], preferred_element_type=jnp.float32)
        .reshape(o_ref.shape)
        .astype(o_ref.dtype)
    )


def _pick_gemm_batch_tile(
    n, hp, wp, cin, oh, ow, cout, kh, kw, itemsize, budget=10 * 2**20
) -> int:
    """Largest batch tile whose working set fits VMEM: the x block, all
    kh*kw live window slices PLUS the concatenated patch tile (both f32
    — _flatten_pixels round-trips packed dtypes), the f32 dot result,
    and the out block. Lane(128)/sublane padding counted like
    pallas_ops._pick_batch_tile."""
    lane = lambda c: -(-c // 128) * 128
    s_mult = 8 * max(4 // itemsize, 1)
    sub = lambda s: -(-s // s_mult) * s_mult
    k_flat = kh * kw * cin
    per_sample = (
        hp * sub(wp) * lane(cin) * itemsize       # x block
        + kh * kw * oh * ow * lane(cin) * 4       # live window slices (f32)
        + oh * ow * lane(k_flat) * 4              # patch tile (f32)
        + oh * ow * lane(cout) * 4                # f32 dot result
        + oh * sub(ow) * lane(cout) * itemsize    # out block
    )
    bn = max(1, min(n, budget // max(per_sample, 1)))
    while n % bn:
        bn -= 1
    return bn


def _conv1_gemm(x: jnp.ndarray, w: jnp.ndarray, oh: int, ow: int):
    """Stride-1 valid conv via the implicit-GEMM kernel; x pre-padded."""
    n, hp, wp, cin = x.shape
    kh, kw, _, cout = w.shape
    bn = _pick_gemm_batch_tile(
        n, hp, wp, cin, oh, ow, cout, kh, kw, x.dtype.itemsize
    )
    w_flat = w.reshape(kh * kw * cin, cout)
    kernel = functools.partial(_conv1_gemm_kernel, kh=kh, kw=kw, oh=oh, ow=ow)
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec(
                (bn, hp, wp, cin), lambda i: (i, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (kh * kw * cin, cout), lambda i: (0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (bn, oh, ow, cout), lambda i: (i, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, cout), x.dtype),
        interpret=pallas_interpret(),
    )(x, w_flat)


def _conv_gemm_forward(x, w, stride: int, padding: int):
    if stride != 1:
        raise ValueError(
            f"conv2d_pallas_gemm is the stride-1 formulation (got stride "
            f"{stride}); strided convs use conv2d_pallas's space-to-batch "
            "direct path"
        )
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    oh = h + 2 * padding - kh + 1
    ow = wd + 2 * padding - kw + 1
    xp = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    return _conv1_gemm(xp[:, : oh + kh - 1, : ow + kw - 1, :], w, oh, ow)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv2d_pallas_gemm(x, w, stride: int = 1, padding: int = 0):
    """Implicit-GEMM conv forward (stride-1): same contract as
    conv2d_pallas — x: (N,H,W,Cin), w: (kh,kw,Cin,Cout) — different MXU
    feeding. Backward shares pallas_ops' kernels (the formulation choice
    is forward-only)."""
    return _conv_gemm_forward(x, w, stride, padding)


def _gemm_fwd(x, w, stride, padding):
    return _conv_gemm_forward(x, w, stride, padding), (x, w)


conv2d_pallas_gemm.defvjp(_gemm_fwd, _conv_bwd)
