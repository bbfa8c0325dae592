"""Fused paged-attention decode kernel for TPU (Pallas).

The XLA formulation of the paged read (serve/paged_cache.py's gather
path) materializes every slot's gathered (B, L, Hkv, hd) cache rows in
HBM before `attend_kv` touches them — per layer, per tick. This kernel
is the FlashAttention discipline (ops/pallas_attention.py) applied to
the PagedAttention layout (Kwon et al., SOSP '23): consume the page pool
+ block tables directly, stream each page HBM -> VMEM, and fold it into
an online-softmax carry, so the gathered rows never exist outside VMEM.

Shape contract (the one `paged_update_attend` already speaks):

- q: (B, kk, H, hd) — kk = 1 is the decode tick, kk = chunk the
  prefill chunk or speculative verify; H % Hkv == 0 (GQA/MQA served by
  the same head mapping as `attend_kv`'s reshape: query head h serves
  kv head h // (H // Hkv)).
- pages: per-layer dicts {k, v} of (num_pages, page_size, Hkv, hd)
  (+ f32 absmax scales {ks, vs} of (num_pages, page_size, Hkv, 1) for
  the int8 form — the cache's quantization contract, applied exactly
  as attend_kv applies it: a k-row's scale multiplies the logits after
  the QK dot, a v-row's folds into the probabilities before the PV
  dot).
- block_table: (B, npages) int32, SCALAR PREFETCH: the page index of
  every grid step is known before the body runs, so the Pallas pipeline
  double-buffers the per-page copies (page i+1's DMA is in flight while
  page i folds). positions: (B, kk) int32.

Grid: (B, npages), pages innermost/sequential. One step DMAs one whole
page — all kv heads, a (page_size, Hkv, hd) block whose last two
dimensions are the pool's own (the only page block Mosaic accepts for
every Hkv: a one-head (ps, 1, hd) block of an Hkv > 1 pool is neither
(8, 128)-divisible nor whole) — and folds each kv head's (ps, hd) rows
into that head's (m, l, acc) carry in VMEM scratch. Online, not exact,
softmax: a (g*kk, L) logits strip would need page-granular writes along
the lane axis, which Mosaic only takes at 128-lane alignment.

What the kernel reads besides pages is laid out for the vector unit,
not taken from SMEM: positions arrive as (B, g*kk, 128) int32 rows
(Mosaic loads scalars, not vectors, from SMEM), and the int8 scales —
3% of the cache bytes — are gathered by XLA into (B, npages, Hkv, ps)
so a head's scales are one lane-dense row beside its logits (the pool's
trailing-1 scale layout would DMA one lane in 128).

Precision: pages convert to f32 on load and both dots run at HIGHEST,
so the f32 cache is f32-accurate on the MXU (its default precision
rounds operands to bf16) and bf16/int8 caches lose nothing beyond their
storage rounding — the same contract as attend_kv under
jax.default_matmul_precision("highest"), which is what chip_smoke.py
compares against on the chip. tests/test_paged_kernel.py pins the kernel
to the gather path in interpret mode on CPU within a few f32 ulp (the
online carry reorders the softmax reduction).

Not tuned: one page per grid step makes B * npages small steps per
layer per tick. Whether it beats the gather anywhere is ROADMAP S4's
question; this file's job is to compile for every head layout and be
right.
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

_LANES = 128


def _run_kernel(kern, grid_spec, out_shape, operands):
    """The one pallas_call site — also the MCT007 producer the lint
    manifest declares for this module's hot driver (`paged_attend`)."""
    return pl.pallas_call(
        kern, grid_spec=grid_spec, out_shape=out_shape,
        interpret=pallas_interpret(),
    )(*operands)


def _dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _paged_kernel(tbl_ref, q_ref, pos_ref, k_ref, v_ref, *refs, npages,
                  page_size, hkv, int8):
    """One (slot, page) grid step: fold page block_table[b, i] into
    every kv head's online-softmax carry; normalize on the last page."""
    if int8:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    i = pl.program_id(1)
    ps = page_size
    gkk, hd = q_ref.shape[2], q_ref.shape[3]

    @pl.when(i == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    # Row r attends key positions <= its own; the page's keys sit at
    # [i*ps, (i+1)*ps). Key 0 is visible to every row (positions >= 0),
    # so m is finite after page 0 and a fully masked later page folds
    # as p == 0.
    key = i * ps + jax.lax.broadcasted_iota(jnp.int32, (gkk, ps), 1)
    mask = key <= pos_ref[0][:, :1]
    for h in range(hkv):
        kp = k_ref[0, :, h, :].astype(jnp.float32)       # (ps, hd)
        vp = v_ref[0, :, h, :].astype(jnp.float32)
        s = _dot(q_ref[0, h], kp, ((1,), (1,))) * scale  # (gkk, ps)
        if int8:
            s = s * ks_ref[0, 0, h:h + 1, :]
        s = jnp.where(mask, s, NEG_INF)
        m = m_ref[h][:, :1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l_ref[h][:, :1] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[h] = jnp.broadcast_to(m_new, (gkk, _LANES))
        l_ref[h] = jnp.broadcast_to(l_new, (gkk, _LANES))
        if int8:
            p = p * vs_ref[0, 0, h:h + 1, :]
        acc_ref[h] = acc_ref[h] * alpha + _dot(p, vp, ((1,), (0,)))

    @pl.when(i == npages - 1)
    def _():
        for h in range(hkv):
            o_ref[0, h] = acc_ref[h] / l_ref[h][:, :1]


def paged_attend(q, c, positions, block_table, page_size: int):
    """Fused paged-attention read over one layer's page pools.

    q: (B, kk, H, hd); c: the layer's page dict (k/v [+ ks/vs]);
    positions: (B, kk) absolute positions; block_table: (B, npages).
    Returns (B, kk, H*hd) f32 — the drop-in replacement for the gather
    + attend_kv pair in serve/paged_cache.paged_update_attend (same
    mask semantics: row j attends key positions <= positions[b, j];
    rows beyond a slot's written extent read whatever the pages hold,
    masked out of the softmax exactly as the gather path does).
    """
    b, kk, h, hd = q.shape
    hkv = c["k"].shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    g = h // hkv
    gkk = g * kk
    npages = block_table.shape[1]
    ps = page_size
    int8 = c["k"].dtype == jnp.int8
    block_table = block_table.astype(jnp.int32)
    # Head-group layout: (B, Hkv, g*kk, hd), rows g-major within a kv
    # head — the same (hkv, g) split attend_kv's reshape uses.
    qg = q.astype(jnp.float32).reshape(b, kk, hkv, g, hd).transpose(
        0, 2, 3, 1, 4).reshape(b, hkv, gkk, hd)
    # Row r = gi*kk + j sits at positions[b, j]; lane-replicated so the
    # kernel reads a (gkk, 1) column with a plain vector load.
    pos_rows = jnp.broadcast_to(
        positions.astype(jnp.int32)[:, None, :, None],
        (b, g, kk, _LANES)).reshape(b, gkk, _LANES)

    def slot_map(b_, i_, tbl):
        return b_, 0, 0, 0

    def page_map(b_, i_, tbl):
        return tbl[b_, i_], 0, 0, 0

    q_spec = pl.BlockSpec((1, hkv, gkk, hd), slot_map)
    page_spec = pl.BlockSpec((1, ps, hkv, hd), page_map)
    in_specs = [
        q_spec,
        pl.BlockSpec((1, gkk, _LANES), lambda b_, i_, tbl: (b_, 0, 0)),
        page_spec,
        page_spec,
    ]
    operands = [block_table, qg, pos_rows, c["k"], c["v"]]
    if int8:
        scale_spec = pl.BlockSpec((1, 1, hkv, ps),
                                  lambda b_, i_, tbl: (b_, i_, 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [
            c[name][block_table][..., 0].transpose(0, 1, 3, 2)
            for name in ("ks", "vs")
        ]

    kern = functools.partial(
        _paged_kernel, npages=npages, page_size=ps, hkv=hkv, int8=int8,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, npages),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, gkk, _LANES), jnp.float32),   # running max
            pltpu.VMEM((hkv, gkk, _LANES), jnp.float32),   # running denom
            pltpu.VMEM((hkv, gkk, hd), jnp.float32),       # acc
        ],
    )
    with annotate("ops.paged_attention"):
        out = _run_kernel(
            kern, grid_spec,
            jax.ShapeDtypeStruct((b, hkv, gkk, hd), jnp.float32),
            operands,
        )
    # (B, Hkv, g, kk, hd) -> (B, kk, H*hd): head order (hkv, g) matches
    # attend_kv's output reshape, so the two paths agree row-for-row.
    return out.reshape(b, hkv, g, kk, hd).transpose(0, 3, 1, 2, 4).reshape(
        b, kk, h * hd)
