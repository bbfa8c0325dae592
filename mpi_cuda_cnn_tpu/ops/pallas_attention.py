"""Fused flash-attention forward kernel for TPU (Pallas).

The jnp-level `blockwise_attention` (ops/attention.py) already has the
right algorithm — online softmax over key/value blocks — but materializes
each (S, block) logit slab in HBM-visible intermediates and leans on XLA
to fuse. This kernel is the fused form: one Pallas program per
(batch*head, q-block) computes its whole output tile with the logits
living only in registers/VMEM — O(BLK_Q * BLK_K) live logits instead of
O(S^2) — and the (m, l, acc) online-softmax carry never leaves VMEM.

Layout: q/k/v arrive (B, S, H, D) (the framework's SP-friendly layout),
kernel works on (B*H, S, D) over a (batch*head, q-block, k-block) grid —
the k-block axis is innermost/sequential and the carry persists in VMEM
scratch, so VMEM stays O(BLK) regardless of S (32k+ context on one chip).
GQA (k/v with Hkv < H heads) switches to a 5-D (b, hkv, group, q-block,
k-block) grid whose index maps are pure mul/add — each kv head serves
its query group zero-copy, and no map ever needs div/mod on a grid
coordinate.
Compute is (BLK_Q, D) @ (D, BLK_K) MXU contractions with f32 accumulators.
Dtype policy: f32 inputs run at HIGHEST precision (~1e-6 vs a float64
reference — the default-precision XLA oracle sits at ~1e-2); bf16 inputs
stay bf16 operands on the MXU's native bf16 x bf16 -> f32 path (~4x the
f32 matmul throughput — the training configuration), with the softmax,
online-carry, and output accumulation still f32. Causal masking uses 2-D
broadcasted_iota and skips blocks fully above the diagonal.

Backward: fused too — a dq kernel (q-rows outer, k-blocks streamed) and a
dk/dv kernel (k-rows outer, q-blocks streamed), with the softmax
probabilities reconstructed exactly from the forward's saved per-row
logsumexp (p = exp(s - L); causal masking falls out as exp(NEG_INF - L)
= 0). O(block) memory end to end; gradient accuracy ~4e-5 of a float64
reference on TPU (PERF.md). The reference never wrote ANY attention
(SURVEY.md §5.7) — this kernel exists for the framework's long-context
path, as the fused twin of ops/attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.trace import annotate
from ..utils.backend import pallas_interpret
from .attention import NEG_INF

# Tuned on v5e (s=8192, d=64): large blocks amortize per-grid-step
# overhead; (512, 1024) ran ~1.5x faster than the XLA oracle at equal
# (HIGHEST) precision, and ~2x larger blocks exhaust scoped VMEM.
BLK_Q = 512
BLK_K = 1024
# bf16 operands halve the VMEM per element: (1024, 1024) fits and runs
# ~25% faster than (512, 1024) (measured s=2048: 2.69 vs 3.64 ms fwd;
# s=8192: 4.6 vs 5.9). (2048, 2048) exhausts VMEM and fails to compile.
BLK_Q_BF16 = 1024
BLK_K_BF16 = 1024


def _blocks(dtype) -> tuple[int, int]:
    if dtype == jnp.bfloat16:
        return BLK_Q_BF16, BLK_K_BF16
    return BLK_Q, BLK_K


def _dot(a, b, dims, hi: bool):
    """MXU contraction with f32 accumulation. hi=True adds HIGHEST
    precision — right for f32 inputs (the kernel's original accuracy
    contract); for bf16 inputs the default precision IS the native
    bf16 x bf16 -> f32 MXU path (~4x the f32 throughput), and HIGHEST
    would force f32 upconversion passes."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if hi else None,
    )


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, causal, nk, scale, pid=(1, 2)
):
    """One (batch*head, q-block, k-block) grid step.

    The k-block axis is the INNERMOST grid dim — sequential on TPU — and
    the online-softmax carry (acc, m, l) lives in VMEM scratch that
    persists across those steps: init at kj == 0, fold one (BLK_Q, BLK_K)
    tile, write the normalized output at kj == nk - 1. K/V blocks are
    (BLK_K, D) — VMEM stays O(BLK) regardless of S.
    """
    qi = pl.program_id(pid[0])
    kj = pl.program_id(pid[1])
    q = q_ref[0]                                   # (BLK_Q, D)
    blk_q, d = q.shape
    blk_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    hi = q_ref.dtype == jnp.float32

    def fold():
        s = _dot(q, k_ref[0], ((1,), (1,)), hi) * scale  # (BLK_Q, BLK_K)
        if causal:
            qpos = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0
            )
            kpos = kj * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1
            )
            mask = kpos <= qpos
            s = jnp.where(mask, s, NEG_INF)
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        p = jnp.exp(s - m_new)
        if causal:
            # Fully-masked rows keep m == NEG_INF where exp(0) = 1 would
            # count masked keys; zero them so l stays 0.
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_ref[:, :1] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[:, :1] = m_new
        # p rounds to the input dtype for the PV contraction (exact for
        # f32; the standard flash-attention practice for bf16 — the MXU
        # takes bf16 operands, the accumulator stays f32).
        acc_ref[:] = acc_ref[:] * alpha + _dot(
            p.astype(v_ref.dtype), v_ref[0], ((1,), (0,)), hi
        )

    if causal:
        # Blocks fully above the diagonal contribute nothing: skip them
        # (they still iterate — the win is skipped FLOPs, ~2x).
        pl.when(kj * blk_k <= qi * blk_q + blk_q - 1)(fold)
    else:
        fold()

    @pl.when(kj == nk - 1)
    def _():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # Per-row logsumexp, saved for the fused backward: p can be
        # reconstructed exactly as exp(s - L) without re-running the
        # online recurrence. Stored (1, 8, blk_q) — the sublane dim is
        # padded to 8 because Pallas blocks need (8, 128)-divisible tails.
        lse = m_ref[:, 0] + jnp.log(l[:, 0])
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, lse.shape[0]))


def _pick_block(s: int, cap: int) -> int:
    """Largest multiple of 128 that divides s, capped at `cap`."""
    b = min(cap, s)
    b -= b % 128
    while b > 128 and s % b:
        b -= 128
    return b


def _to_rows(t, b, h, s, d):
    return t.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_rows(t, b, h, s, d):
    return t.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _gqa_maps(h: int, hkv: int):
    """Index maps for the GQA 5-D grid (b, hkv, g, blkA, blkB): query
    rows live at b*H + kvh*g + gi, kv rows at b*Hkv + kvh — all mul/add
    (a fused (b*H,) grid would need div/mod in the maps, which Mosaic
    compiles pathologically slowly at large grids: measured minutes-long
    hangs at s >= 8192). blkA/blkB pick their grid coordinate per kernel
    via the returned lambdas' last two axes."""
    g = h // hkv

    def q_rows(axis):  # row from (b, kvh, gi); seq block from grid[axis]
        def index_map(b, kvh, gi, i, j):
            return b * h + kvh * g + gi, (i if axis == 3 else j), 0
        return index_map

    def kv_rows(axis):
        def index_map(b, kvh, gi, i, j):
            return b * hkv + kvh, (i if axis == 3 else j), 0
        return index_map

    def lse_rows(axis):  # (rows, 8, s) layout: block index in slot 2
        def index_map(b, kvh, gi, i, j):
            return b * h + kvh * g + gi, 0, (i if axis == 3 else j)
        return index_map

    return q_rows, kv_rows, lse_rows


def _flash_forward(q, k, v, causal: bool, *, with_lse: bool = False,
                   out_f32: bool = False):
    """out_f32 keeps the f32 kernel output uncast — for callers (the
    ring-flash fold) that merge partials in f32; casting each per-hop
    partial to a bf16 input dtype would accumulate truncation error.

    GQA: k/v may carry Hkv < H heads (H % Hkv == 0); the kernel reads
    each kv head for its query-head group via the block index map."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    if s % 128:
        raise ValueError(f"seq len {s} must be a multiple of 128")
    orig_dtype = q.dtype
    bq, bk = _blocks(orig_dtype)
    blk_q = _pick_block(s, bq)
    blk_k = _pick_block(s, bk)
    # bf16 inputs stay bf16 into the kernel (native MXU operands, f32
    # accumulators/softmax inside — ~4x the f32 matmul throughput);
    # anything else computes in f32 at HIGHEST precision (the original
    # accuracy contract: ~1e-6 of a float64 reference).
    kdt = jnp.bfloat16 if orig_dtype == jnp.bfloat16 else jnp.float32
    qr = _to_rows(q.astype(kdt), b, h, s, d)
    kr = _to_rows(k.astype(kdt), b, hkv, s, d)
    vr = _to_rows(v.astype(kdt), b, hkv, s, d)

    nk = s // blk_k
    if hkv == h:
        grid = (b * h, s // blk_q, nk)
        pid = (1, 2)
        q_map = lambda bh, i, j: (bh, i, 0)
        kvm = lambda bh, i, j: (bh, j, 0)
        lse_map = lambda bh, i, j: (bh, 0, i)
    else:
        g_ = h // hkv
        grid = (b, hkv, g_, s // blk_q, nk)
        pid = (3, 4)
        q_rows, kv_rows, lse_rows = _gqa_maps(h, hkv)
        q_map = q_rows(3)
        kvm = kv_rows(4)
        lse_map = lse_rows(3)
    kernel = functools.partial(
        _flash_kernel, causal=causal, nk=nk, scale=1.0 / (d ** 0.5),
        pid=pid,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, d), kvm, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, blk_k, d), kvm, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, d), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, blk_q), lse_map, memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, 8, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, d), jnp.float32),    # acc
            pltpu.VMEM((blk_q, 128), jnp.float32),  # running max (col 0)
            pltpu.VMEM((blk_q, 128), jnp.float32),  # running denom (col 0)
        ],
        interpret=pallas_interpret(),
    )(qr, kr, vr)
    out = _from_rows(out, b, h, s, d)
    if not out_f32:
        out = out.astype(orig_dtype)
    return (out, lse[:, 0, :]) if with_lse else out


# ---------------------------------------------------------------------------
# Fused backward: dq kernel (rows x streamed k-blocks) + dk/dv kernel
# (k-rows x streamed q-blocks). p is reconstructed exactly from the saved
# logsumexp (p = exp(s - L)); causal masking falls out of s = NEG_INF ->
# p = 0 with finite L. All accumulators live in VMEM scratch: O(block)
# memory, like the forward.
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, dq_ref, acc_ref,
    *, causal, nk, scale, pid=(1, 2)
):
    qi = pl.program_id(pid[0])
    kj = pl.program_id(pid[1])
    q = q_ref[0]
    blk_q, d = q.shape
    blk_k = k_ref.shape[1]

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    hi = q_ref.dtype == jnp.float32

    def fold():
        s = _dot(q, k_ref[0], ((1,), (1,)), hi) * scale
        if causal:
            qpos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kj * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        # lse/dvec arrive column-oriented: (1, blk_q, 8) with the row
        # value replicated along the narrow lane dim; [:, :1] is the
        # (blk_q, 1) column.
        p = jnp.exp(s - lse_ref[0][:, :1])
        dov = _dot(do_ref[0], v_ref[0], ((1,), (1,)), hi)
        ds = p * (dov - dvec_ref[0][:, :1]) * scale
        acc_ref[:] += _dot(ds.astype(k_ref.dtype), k_ref[0], ((1,), (0,)), hi)

    if causal:
        pl.when(kj * blk_k <= qi * blk_q + blk_q - 1)(fold)
    else:
        fold()

    @pl.when(kj == nk - 1)
    def _():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dvec_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, causal, nq, scale, pid=(1, 2)
):
    ki = pl.program_id(pid[0])
    qj = pl.program_id(pid[1])
    k = k_ref[0]
    blk_k, d = k.shape
    blk_q = q_ref.shape[1]

    @pl.when(qj == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    hi = q_ref.dtype == jnp.float32

    def fold():
        # Transposed tile: rows = this program's keys, lanes = queries.
        s_t = _dot(k, q_ref[0], ((1,), (1,)), hi) * scale  # (blk_k, blk_q)
        if causal:
            kpos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 0)
            qpos = qj * blk_q + jax.lax.broadcasted_iota(jnp.int32, s_t.shape, 1)
            s_t = jnp.where(kpos <= qpos, s_t, NEG_INF)
        # lse/dvec arrive lane-oriented: (1, 8, blk_q); row 0 of the
        # sublane padding is the (blk_q,) lane vector.
        p_t = jnp.exp(s_t - lse_ref[0, 0, :][None, :])
        dv_acc[:] += _dot(p_t.astype(do_ref.dtype), do_ref[0], ((1,), (0,)), hi)
        vdo = _dot(v_ref[0], do_ref[0], ((1,), (1,)), hi)  # (blk_k, blk_q)
        ds_t = p_t * (vdo - dvec_ref[0, 0, :][None, :]) * scale
        dk_acc[:] += _dot(ds_t.astype(q_ref.dtype), q_ref[0], ((1,), (0,)), hi)

    if causal:
        # Queries strictly before this key block are fully masked.
        pl.when(qj * blk_q + blk_q - 1 >= ki * blk_k)(fold)
    else:
        fold()

    @pl.when(qj == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, causal: bool, *, grads_f32: bool = False):
    """grads_f32 keeps the f32 kernel gradients uncast — for callers (the
    ring-flash backward) that ACCUMULATE partials across hops in f32;
    rounding each per-hop partial to a bf16 input dtype first would
    collect p truncation errors instead of one."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    bq, bk = _blocks(q.dtype)
    blk_q = _pick_block(s, bq)
    blk_k = _pick_block(s, bk)
    scale = 1.0 / (d ** 0.5)
    # Same dtype policy as the forward: bf16 operands stay bf16 into the
    # kernels (native MXU path), everything else f32 at HIGHEST.
    kdt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
    qr, orr, gr = (
        _to_rows(t.astype(kdt), b, h, s, d) for t in (q, o, g)
    )
    kr = _to_rows(k.astype(kdt), b, hkv, s, d)
    vr = _to_rows(v.astype(kdt), b, hkv, s, d)
    # D_i = rowsum(dO_i * O_i) — elementwise, O(S*D), always f32.
    dvec = jnp.sum(
        gr.astype(jnp.float32) * orr.astype(jnp.float32), axis=-1
    )                                                # (b*h, s)
    # Two orientations of the per-row vectors, so neither kernel pays a
    # sublane<->lane relayout: columns for the dq kernel, lanes for the
    # dk/dv kernel. Both are NARROW (8-wide minor dim, not 128): the
    # kernels only read lane/sublane 0, so HBM holds 8 replicas (the f32
    # sublane tile) instead of a full 128-lane broadcast — 16x less HBM
    # footprint/bandwidth for these side inputs; Mosaic lane-pads the
    # (blk_q, 8) tile on load.
    lse_col = jnp.broadcast_to(lse[:, :, None], (b * h, s, 8))
    dvec_col = jnp.broadcast_to(dvec[:, :, None], (b * h, s, 8))
    lse_row = jnp.broadcast_to(lse[:, None, :], (b * h, 8, s))
    dvec_row = jnp.broadcast_to(dvec[:, None, :], (b * h, 8, s))

    # Grid layout mirrors the forward: 3-D per-(b*h) for MHA; a 5-D
    # (b, hkv, g, blkA, blkB) grid for GQA so every index map stays
    # mul/add (div/mod in maps stalls Mosaic's compile at large grids).
    if hkv == h:
        dq_grid = (b * h, s // blk_q, s // blk_k)
        kv_grid = (b * h, s // blk_k, s // blk_q)
        pid = (1, 2)
        q_map = lambda bh, i, j: (bh, i, 0)
        q_stream_map = lambda bh, i, j: (bh, j, 0)
        kv_map = q_stream_map
        kv_row_map = q_map
        rows_map = lambda bh, i, j: (bh, 0, j)
    else:
        g_ = h // hkv
        dq_grid = (b, hkv, g_, s // blk_q, s // blk_k)
        kv_grid = (b, hkv, g_, s // blk_k, s // blk_q)
        pid = (3, 4)
        q_rows, kv_rows, lse_rows = _gqa_maps(h, hkv)
        q_map = q_rows(3)         # q/dq rows, block from grid[3]
        q_stream_map = q_rows(4)  # q/do streamed on grid[4] (dkv kernel)
        kv_map = kv_rows(4)       # k/v streamed on grid[4] (dq kernel)
        kv_row_map = kv_rows(3)   # k/v rows on grid[3] (dkv kernel)
        rows_map = lse_rows(4)

    q_spec = pl.BlockSpec((1, blk_q, d), q_map, memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((1, blk_q, 8), q_map, memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, blk_k, d), kv_map, memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, nk=s // blk_k,
                          scale=scale, pid=pid),
        grid=dq_grid,
        in_specs=[q_spec, k_spec, k_spec, q_spec, col_spec, col_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        interpret=pallas_interpret(),
    )(qr, kr, vr, gr, lse_col, dvec_col)

    # dk/dv: k-rows outer, q-blocks streamed innermost. The grid stays
    # per QUERY head; under GQA each kv head's gradient is produced as
    # H/Hkv per-qhead partial rows (racing writes to one shared kv row
    # are not expressible) and group-summed after the kernel — the
    # OUTPUT rows therefore index by query head in both layouts.
    kq_in_spec = pl.BlockSpec((1, blk_k, d), kv_row_map,
                              memory_space=pltpu.VMEM)
    # Output rows index by QUERY head with the block on grid[3] — which
    # is exactly q_map in both layouts (MHA: q rows == kv rows).
    kq_out_spec = pl.BlockSpec((1, blk_k, d), q_map,
                               memory_space=pltpu.VMEM)
    qs_spec = pl.BlockSpec((1, blk_q, d), q_stream_map,
                           memory_space=pltpu.VMEM)
    rows_spec = pl.BlockSpec((1, 8, blk_q), rows_map,
                             memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, nq=s // blk_q,
                          scale=scale, pid=pid),
        grid=kv_grid,
        in_specs=[qs_spec, kq_in_spec, kq_in_spec, qs_spec, rows_spec,
                  rows_spec],
        out_specs=[kq_out_spec, kq_out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, d), jnp.float32),
            pltpu.VMEM((blk_k, d), jnp.float32),
        ],
        interpret=pallas_interpret(),
    )(qr, kr, vr, gr, lse_row, dvec_row)

    dq = _from_rows(dq, b, h, s, d)
    if hkv == h:
        dk = _from_rows(dk, b, h, s, d)
        dv = _from_rows(dv, b, h, s, d)
    else:
        # Sum the per-qhead partials within each kv group: rows are
        # ordered b*H with H = Hkv * group, group-major within a batch.
        g_ = h // hkv
        dk = _from_rows(
            dk.reshape(b, hkv, g_, s, d).sum(axis=2).reshape(b * hkv, s, d),
            b, hkv, s, d,
        )
        dv = _from_rows(
            dv.reshape(b, hkv, g_, s, d).sum(axis=2).reshape(b * hkv, s, d),
            b, hkv, s, d,
        )
    return tuple(
        t.astype(jnp.float32 if grads_f32 else ref.dtype)
        for t, ref in ((dq, q), (dk, k), (dv, v))
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q, k, v, causal: bool = False):
    """Fused scaled-dot-product attention. q: (B, S, H, D); k/v:
    (B, S, Hkv, D) with H % Hkv == 0 (Hkv < H = grouped-query attention,
    served zero-copy via the kernel's block index maps). S a multiple of
    128. Exact (online softmax), causal optional. Both the forward and
    backward are fused Pallas kernels with O(block) memory."""
    with annotate("ops.flash_attention"):
        return _flash_forward(q, k, v, causal)


def _fwd(q, k, v, causal):
    out, lse = _flash_forward(q, k, v, causal, with_lse=True)
    return out, (q, k, v, out, lse)


def _bwd(causal, res, g):
    q, k, v, o, lse = res
    return _flash_backward(q, k, v, o, lse, g, causal)


flash_attention.defvjp(_fwd, _bwd)
