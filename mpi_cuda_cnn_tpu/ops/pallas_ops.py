"""Pallas TPU kernels — the accelerator-kernel surface of the framework.

The reference's native surface is one CUDA conv-forward kernel + host
wrapper (conv_forward_kernel CUDAcnn.cu:167-195, forward_convolution_layer
CUDAcnn.cu:198-218): one thread per output element, per-call
cudaMalloc/H2D/D2H round-trips, and no backward (conv bwd and all FC work
stayed on the CPU — SURVEY.md 2.14-2.15). These kernels close that gap the
TPU way:

- data stays HBM/VMEM-resident (no per-call host round-trip — the wrapper
  feeds device arrays straight to pallas_call);
- compute is phrased as MXU matmuls, not per-element threads: the direct
  conv is a sum over kernel positions of (batch*out_pixels, Cin) @
  (Cin, Cout) contractions accumulated in an f32 VMEM scratch;
- strided convs are decomposed space-to-batch style in the wrapper: a
  stride-s conv is the sum of s*s stride-1 convs over phase-shifted inputs
  with phase-sliced kernels (Mosaic vectors don't do strided extracts, and
  stride-1 is what the MXU formulation wants anyway); the phase slicing is
  zero-FLOP XLA glue, every MAC runs in the Pallas kernel;
- backward exists: d(input) reuses the SAME stride-1 forward kernel on the
  stride-dilated cotangent with the spatially-flipped, in/out-transposed
  kernel (the transposed-conv identity), and d(kernel) is its own
  batch-accumulating kernel (phase-decomposed the same way);
- everything is wired into jax.custom_vjp, so `jax.grad` of a model using
  backend="pallas" differentiates through these kernels.

On platform cpu the kernels run in Pallas interpreter mode
(utils/backend.pallas_interpret), so the whole suite is testable on the
CPU mesh (tests/test_pallas.py checks
parity against the XLA oracle ops).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.backend import pallas_interpret


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Dense: tiled MXU matmul
# ---------------------------------------------------------------------------

_BM = 128  # rows per program (MXU-aligned)
_BN = 128  # cols per program


def _matmul_kernel(x_ref, w_ref, o_ref):
    o_ref[:] = jnp.dot(
        x_ref[:], w_ref[:], preferred_element_type=jnp.float32
    ).astype(o_ref.dtype)


def _matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """(M, K) @ (K, N) on the MXU, tiled (BM, K)x(K, BN) per program.

    K is kept whole per program (our models' K <= ~4k: the (BM, K) and
    (K, BN) blocks fit VMEM comfortably); M and N are padded to tile
    multiples and sliced back.
    """
    m, k = x.shape
    _, n = w.shape
    mp, np_, kp = _round_up(m, _BM), _round_up(n, _BN), _round_up(k, 8)
    xp = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    wp = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    out = pl.pallas_call(
        _matmul_kernel,
        grid=(mp // _BM, np_ // _BN),
        in_specs=[
            pl.BlockSpec((_BM, kp), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((kp, _BN), lambda i, j: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (_BM, _BN), lambda i, j: (i, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        interpret=pallas_interpret(),
    )(xp, wp)
    return out[:m, :n]


@jax.custom_vjp
def dense_pallas(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """FC forward on the MXU: the Pallas twin of Layer_feedForw_full's MAC
    loop (cnn.c:113-123)."""
    return _matmul(x, w) + b


def _dense_fwd(x, w, b):
    return dense_pallas(x, w, b), (x, w)


def _dense_bwd(res, g):
    """FC backward (the Pallas twin of Layer_feedBack_full, cnn.c:154-173):
    dx = g @ w^T (error propagation), dw = x^T @ g (u_weights
    accumulation), db = sum(g)."""
    x, w = res
    g = g.astype(x.dtype)
    dx = _matmul(g, w.T)
    dw = _matmul(x.T, g)
    db = jnp.sum(g, axis=0)
    return dx, dw, db


dense_pallas.defvjp(_dense_fwd, _dense_bwd)


# ---------------------------------------------------------------------------
# Conv: stride-1 direct convolution kernels + space-to-batch wrappers
# ---------------------------------------------------------------------------


def _flatten_pixels(xs, m, cin):
    """(BN, OH, OW, Cin) window slice -> (BN*OH*OW, Cin) matmul operand.

    Packed dtypes (bf16) can't reshape across the sublane dim directly —
    Mosaic rejects e.g. vector<8x7x7x16xbf16> -> vector<392x16xbf16> — so
    the reshape goes through f32 (lossless for bf16) and casts back for
    the MXU."""
    if xs.dtype == jnp.float32:
        return xs.reshape(m, cin)
    return xs.astype(jnp.float32).reshape(m, cin).astype(xs.dtype)


def _conv1_kernel(x_ref, w_ref, o_ref, acc_ref, *, kh, kw, oh, ow):
    """One batch-tile of stride-1 valid direct conv.

    x_ref: (BN, Hp, Wp, Cin) block in VMEM, Hp >= oh+kh-1, Wp >= ow+kw-1.
    w_ref: (kh, kw, Cin, Cout) kernel.
    o_ref: (BN, OH, OW, Cout).
    For each kernel offset (ky, kx): unit-stride window slice, flatten
    pixels, accumulate an MXU contraction — the same arithmetic as the
    CUDA kernel's per-thread triple loop (CUDAcnn.cu:179-191), phrased as
    (BN*OH*OW, Cin) @ (Cin, Cout) matmuls.

    Index discipline: ky advances via fori_loop — a dynamic offset, legal
    because H is an untiled dim (so is w's kh) — while kx is a static
    Python unroll: dim 2 is the sublane dim, where Mosaic cannot prove
    alignment of dynamic offsets for packed dtypes (bf16's (16, 128)
    tiling). The loop also keeps at most kw window slices live at a time;
    with small cin the lane-padded slices are large, and unrolling all
    kh*kw of them overflows VMEM.
    """
    bn = x_ref.shape[0]
    cin = x_ref.shape[3]
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(ky, _):
        for kx in range(kw):
            xs = _flatten_pixels(
                x_ref[:, pl.ds(ky, oh), kx : kx + ow, :], bn * oh * ow, cin
            )
            acc_ref[:] += jnp.dot(
                xs, w_ref[ky, kx], preferred_element_type=jnp.float32
            )
        return 0

    jax.lax.fori_loop(0, kh, body, 0)
    o_ref[:] = acc_ref[:].reshape(o_ref.shape).astype(o_ref.dtype)


def _pick_batch_tile(
    n, hp, wp, cin, oh, ow, cout, kw, itemsize, budget=8 * 2**20
) -> int:
    """Largest batch tile whose VMEM working set fits the scoped limit.

    Counts what actually occupies VMEM, with the (8, 128)
    sublane/lane padding Mosaic stores blocks with: the x and out blocks,
    up to kw+1 live f32 window slices (_flatten_pixels round-trips packed
    dtypes through f32, and the kx unroll keeps kw slices in flight), and
    the f32 accumulator. The naive 4*elements estimate under-counted
    lane padding ~8x for small channel counts and OOM'd the 16M scoped
    vmem on the bf16 backward."""
    lane = lambda c: -(-c // 128) * 128
    # Packed dtypes tile (16, 128), f32 (8, 128); >=4-byte dtypes all (8, 128).
    s_mult = 8 * max(4 // itemsize, 1)
    sub = lambda s: -(-s // s_mult) * s_mult
    per_sample = (
        hp * sub(wp) * lane(cin) * itemsize        # x block
        + (kw + 1) * oh * ow * lane(cin) * 4       # live window slices (f32)
        + oh * ow * lane(cout) * 4                 # f32 accumulator
        + oh * sub(ow) * lane(cout) * itemsize     # out / cotangent block
    )
    bn = max(1, min(n, budget // max(per_sample, 1)))
    while n % bn:
        bn -= 1
    return bn


def _conv1(x: jnp.ndarray, w: jnp.ndarray, oh: int, ow: int) -> jnp.ndarray:
    """Stride-1 valid conv via the Pallas kernel; x is already padded."""
    n, hp, wp, cin = x.shape
    kh, kw, _, cout = w.shape
    bn = _pick_batch_tile(n, hp, wp, cin, oh, ow, cout, kw, x.dtype.itemsize)
    kernel = functools.partial(_conv1_kernel, kh=kh, kw=kw, oh=oh, ow=ow)
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec(
                (bn, hp, wp, cin), lambda i: (i, 0, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (kh, kw, cin, cout),
                lambda i: (0, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (bn, oh, ow, cout), lambda i: (i, 0, 0, 0), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, cout), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn * oh * ow, cout), jnp.float32)],
        interpret=pallas_interpret(),
    )(x, w)


def _phases(xp, w, stride):
    """Space-to-batch decomposition: yield (phase input, phase kernel) pairs
    such that the stride-s conv of the original equals the SUM of stride-1
    valid convs of the pairs. The phase slicing is zero-FLOP XLA glue."""
    kh, kw = w.shape[0], w.shape[1]
    for ry in range(min(stride, kh)):
        for rx in range(min(stride, kw)):
            wk = w[ry::stride, rx::stride]
            px = xp[:, ry::stride, rx::stride, :]
            yield px, wk


def _conv_forward(x, w, stride: int, padding: int):
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (wd + 2 * padding - kw) // stride + 1
    # Extra (stride-1) high-side zero pad so every phase grid is complete;
    # the zeros multiply kernel taps beyond the true extent and contribute 0.
    extra = stride - 1
    xp = jnp.pad(
        x,
        ((0, 0), (padding, padding + extra), (padding, padding + extra), (0, 0)),
    )
    if stride == 1:
        return _conv1(xp[:, : oh + kh - 1, : ow + kw - 1, :], w, oh, ow)
    out = None
    for px, wk in _phases(xp, w, stride):
        qh, qw = wk.shape[0], wk.shape[1]
        px = px[:, : oh + qh - 1, : ow + qw - 1, :]
        y = _conv1(px, wk, oh, ow)
        out = y if out is None else out + y
    return out


def _conv1_dw_kernel(x_ref, g_ref, dw_ref, *, kh, kw, oh, ow):
    """d(kernel) of a stride-1 valid conv for one batch tile, accumulated
    across the sequential grid: dw[ky,kx] = x_window^T @ g over all pixels —
    the Pallas twin of the reference's u_weights accumulation
    (cnn.c:238-242). Same index discipline as _conv1_kernel: dynamic ky on
    untiled dims, static kx on the sublane dim."""
    i = pl.program_id(0)
    bn = x_ref.shape[0]
    cin = x_ref.shape[3]
    cout = g_ref.shape[3]

    @pl.when(i == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    gf = _flatten_pixels(g_ref[:], bn * oh * ow, cout)

    def body(ky, _):
        for kx in range(kw):
            xs = _flatten_pixels(
                x_ref[:, pl.ds(ky, oh), kx : kx + ow, :], bn * oh * ow, cin
            )
            dw_ref[ky, kx] += jnp.dot(
                xs.T, gf, preferred_element_type=jnp.float32
            ).astype(dw_ref.dtype)
        return 0

    jax.lax.fori_loop(0, kh, body, 0)


def _conv1_dw(x, g, kh: int, kw: int):
    """dw for a stride-1 valid conv; x already padded/cropped to match g."""
    n, hp, wp, cin = x.shape
    _, oh, ow, cout = g.shape
    bn = _pick_batch_tile(n, hp, wp, cin, oh, ow, cout, kw, x.dtype.itemsize)
    kernel = functools.partial(_conv1_dw_kernel, kh=kh, kw=kw, oh=oh, ow=ow)
    dw = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec(
                (bn, hp, wp, cin), lambda i: (i, 0, 0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (bn, oh, ow, cout), lambda i: (i, 0, 0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (kh, kw, cin, cout),
            lambda i: (0, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((kh, kw, cin, cout), jnp.float32),
        interpret=pallas_interpret(),
    )(x, g)
    return dw


def _conv_dw(x, g, stride: int, padding: int, kh: int, kw: int):
    n, h, wd, cin = x.shape
    _, oh, ow, cout = g.shape
    extra = stride - 1
    xp = jnp.pad(
        x,
        ((0, 0), (padding, padding + extra), (padding, padding + extra), (0, 0)),
    )
    if stride == 1:
        dw = _conv1_dw(xp[:, : oh + kh - 1, : ow + kw - 1, :], g, kh, kw)
        return dw.astype(x.dtype)
    dw = jnp.zeros((kh, kw, cin, cout), jnp.float32)
    for ry in range(min(stride, kh)):
        for rx in range(min(stride, kw)):
            qh = len(range(ry, kh, stride))
            qw = len(range(rx, kw, stride))
            px = xp[:, ry::stride, rx::stride, :][:, : oh + qh - 1, : ow + qw - 1, :]
            dphase = _conv1_dw(px, g, qh, qw)
            dw = dw.at[ry::stride, rx::stride].set(dphase)
    return dw.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv2d_pallas(x, w, stride: int = 1, padding: int = 0):
    """Direct conv forward — the TPU twin of conv_forward_kernel
    (CUDAcnn.cu:167-195). x: (N,H,W,Cin), w: (kh,kw,Cin,Cout)."""
    return _conv_forward(x, w, stride, padding)


def _conv_fwd(x, w, stride, padding):
    return _conv_forward(x, w, stride, padding), (x, w)


def _conv_bwd(stride, padding, res, g):
    """Conv backward — the piece the reference never wrote for its GPU path
    (conv bwd stayed CPU-only, SURVEY.md 2.15).

    dx: transposed conv = the SAME stride-1 forward kernel over the
    stride-dilated cotangent with flipped/in-out-transposed weights
    (cnn.c:228-236's scatter, re-expressed as a gather so it stays an MXU
    contraction). dw: the accumulating kernel above.
    """
    x, w = res
    kh, kw, cin, cout = w.shape
    n, h, wd, _ = x.shape
    g = g.astype(x.dtype)

    # Dilate the cotangent by the forward stride (XLA glue; zero FLOPs).
    if stride > 1:
        g_dil = lax.pad(
            g,
            jnp.zeros((), g.dtype),
            ((0, 0, 0), (0, 0, stride - 1), (0, 0, stride - 1), (0, 0, 0)),
        )
    else:
        g_dil = g
    # Pad so the stride-1 valid conv recovers the full (h, wd) input extent.
    ph = kh - 1 - padding
    pw = kw - 1 - padding
    eh = h - (g_dil.shape[1] + 2 * ph - kh + 1)
    ew = wd - (g_dil.shape[2] + 2 * pw - kw + 1)
    g_dil = jnp.pad(g_dil, ((0, 0), (ph, ph + eh), (pw, pw + ew), (0, 0)))
    w_t = jnp.transpose(w[::-1, ::-1, :, :], (0, 1, 3, 2))  # flip + swap io
    dx = _conv1(g_dil, w_t, h, wd)
    dw = _conv_dw(x, g, stride, padding, kh, kw)
    return dx, dw


conv2d_pallas.defvjp(_conv_fwd, _conv_bwd)
