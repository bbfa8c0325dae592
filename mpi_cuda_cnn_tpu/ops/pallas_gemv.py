"""Per-channel int8 weight quantization + the decode GEMV kernel.

With the KV cache already int8 under GQA/MQA (auto dtype routing,
PR 9), the WEIGHT stream is the dominant byte mover of a decode tick:
every weight matrix is read once per token at B = slots, T = 1 — pure
GEMV, bandwidth-bound, zero reuse. This module quarters those bytes
with the same absmax contract the cache uses, applied per OUTPUT
channel: w (din, dout) stores as int8 values + one f32 scale per
column, and the scale — constant along the contracted din — multiplies
the OUTPUT after the dot, never entering the MXU contraction (the
int8-KV discipline of models/generate.init_cache, applied to weights).

Quantization is ONE-TIME (`quantize_decode_params` at engine/bench
construction, keyed off --decode-weights-dtype); the decode hot loop
only ever reads the int8 form. `QuantW` is a registered pytree, so
quantized params flow through the jitted decode programs unchanged,
and `qmatmul` is the single dispatch point the shared decode skeleton
(models/generate.token_forward + transformer.project_qkv/apply_block)
calls for every weight matmul: a plain array takes the `@` it always
took, a QuantW takes the fused Pallas GEMV below. One forward
implementation, two storage formats — exactly the cache's design.

Error contract: per-channel absmax bounds each weight's relative error
by 1/254, and the scales are exact f32 multiplies outside the dot, so
logit error is test-bounded the same way the int8 KV cache's is
(tests/test_paged_kernel.py, 5e-2 band vs f32 weights — the discipline
of test_generate's int8-cache pin). MoE expert banks and the embedding
tables are left in f32: experts route through moe_mlp_inference's own
einsums (a separate lever), and tok_emb/pos_emb are gathers, not GEMVs.

The kernel tiles dout and din: grid (dout/TILE_N, din/TILE_K) with din
innermost, each step one MXU contraction of the (TILE_K, TILE_N) int8
tile with the (N, TILE_K) block of x, accumulated into the resident f32
output tile; the f32 scale row multiplies the finished tile on the last
din step. din is tiled because a whole-din weight block does not fit
the 16 MiB of scoped VMEM at din = 16384 (the 4*d MLP contraction of a
d = 4096 model).

The contraction is ONE bf16 pass over the weights. An int8 value
(|q| <= 127) is exact in bf16, so the tile converts to bf16 and enters
the MXU once, at default precision with f32 accumulation. x is f32 and
must not be rounded (a default-precision f32 dot rounds it to bf16 and
is 2e-3 off), so it is split into three bf16 terms, hi = bf16(x),
mid = bf16(x - hi), lo = bf16(x - hi - mid): 3 x 8 mantissa bits hold
f32's 24, each subtraction is exact, hence hi + mid + lo == x bit for
bit. The terms are stacked as one (3N, TILE_K) left operand, meet the
one weight tile in one dot, and the three row groups of the f32 product
are summed: the same mathematics as an f32 dot at HIGHEST (which splits
BOTH operands three ways and pushes the weights through the MXU three
times, two of them zeros), measured as close to an f64 reference
(PERF.md section 6, PR 26). Dropping mid or lo is a lower precision,
a different result; tests/test_paged_kernel.py holds that line.
Interpret mode (platform cpu) runs the same kernel body.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.backend import pallas_interpret


@dataclasses.dataclass
class QuantW:
    """Per-output-channel int8 weight: values (din, dout) int8, scales
    (1, dout) f32 with w ~= q * s. A registered pytree — jitted decode
    programs carry it like any other param leaf."""

    q: jnp.ndarray
    s: jnp.ndarray

    @property
    def shape(self):
        return self.q.shape


jax.tree_util.register_dataclass(QuantW, data_fields=["q", "s"],
                                 meta_fields=[])


def quantize_weight(w) -> QuantW:
    """Absmax int8 quantization per output channel: w (din, dout) ->
    (int8 values, f32 scales (1, dout)) with w ~= values * scales."""
    wf = jnp.asarray(w, jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=0, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-10)
    q = jnp.clip(jnp.round(wf / s), -127, 127).astype(jnp.int8)
    return QuantW(q=q, s=s)


def dequantize_weight(w: QuantW) -> jnp.ndarray:
    """The f32 form the GEMV is parity-tested against."""
    return w.q.astype(jnp.float32) * w.s


# The decode-path matmul weights quantize_decode_params converts: every
# per-block GEMV (QKV/out/MLP) plus the head — the byte movers of a
# decode tick. Embeddings are gathers; layernorm params are O(dim).
_BLOCK_WEIGHTS = ("wqkv", "wq", "wkv", "wo", "w1", "w2")


def quantize_decode_params(params: dict, dtype: str) -> dict:
    """One-time serving-weights conversion keyed off
    --decode-weights-dtype: "float32" passes through, "bfloat16" casts
    the f32 leaves (the PERF.md-measured serving cast), "int8" replaces
    the decode GEMV matrices with QuantW (per-channel absmax). The
    returned tree feeds the SAME forward as the f32 one — qmatmul
    dispatches on the leaf type, so there is no second decode path."""
    if dtype == "float32":
        return params
    if dtype == "bfloat16":
        return jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if a.dtype == jnp.float32 else a, params,
        )
    if dtype != "int8":
        raise ValueError(
            f"decode weights dtype {dtype!r}: want float32, bfloat16, "
            "or int8 (or 'auto' resolved by pick_weights_dtype first)"
        )
    out = dict(params)
    out["head"] = quantize_weight(params["head"])
    blocks = []
    for blk in params["blocks"]:
        nb = dict(blk)
        for name in _BLOCK_WEIGHTS:
            if name in nb:
                nb[name] = quantize_weight(nb[name])
        blocks.append(nb)
    out["blocks"] = blocks
    return out


# Weight tile caps: (512, 4096) int8 is 2 MiB — 4 MiB double-buffered
# and 4 MiB more as bf16, inside the 16 MiB of scoped VMEM up to 256
# rows of x. The kernel is bound by the tile's DMA, so the tile is wide
# before it is deep: 4096 contiguous bytes a row (a whole row where
# dout = 4096), 32 grid steps for a 64 MiB matrix, and x's (N, 512)
# block, fetched again at every step, is 1.5% of the step's bytes at 16
# rows. A narrow dout deepens the tile to the same bytes (_run_gemv).
# Measured on the v5e against (2048, 512) and others: PERF.md section 6.
_TILE_N = 4096
_TILE_K = 512


def _tile(dim: int, cap: int) -> int:
    """Largest multiple of 128 dividing dim, capped at `cap`; a dim the
    lane width doesn't divide runs as one tile (interpret-mode shapes —
    on TPU, model dims are 128-multiples)."""
    if dim % 128:
        return dim
    t = min(cap, dim)
    while dim % t:
        t -= 128
    return t


def _bf16_terms(x):
    """f32 (n, k) -> bf16 (3n, k), rows [hi; mid; lo] with hi + mid +
    lo == x exactly. Stacked in f32, whose 8-row tiles take any n that
    Mosaic takes, then cast: each term is a bf16 value already."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    mid = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    lo = x - hi - mid
    return jnp.concatenate([hi, mid, lo], axis=0).astype(jnp.bfloat16)


def _gemv_kernel(x_ref, w_ref, s_ref, o_ref, *, n, nk):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)

    p = jax.lax.dot_general(
        _bf16_terms(x_ref[:]), w_ref[:].astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[:] += p[2 * n:] + p[n:2 * n] + p[:n]

    @pl.when(k == nk - 1)
    def _():
        o_ref[:] *= s_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _run_gemv(x, q, s, *, interpret):
    """The one pallas_call site — the MCT007 producer declared for this
    module in the lint manifest. Jitted because the memory-space
    constraint exists only under a trace (`interpret` is static, so the
    trace cache never hands an interpreted body to the chip).

    The weights are held to HBM: the kernel streams them at what the
    memory allows. Left to itself XLA's memory-space assignment
    prefetches whole matrices into VMEM ahead of the call (16-64 MiB
    copy-start/copy-done pairs); that competes with the running
    kernel's own DMA for the same HBM bandwidth, the copy-done waits,
    and the bytes move where `int8_gemv_roofline` does not see them
    (it read 108%; PERF.md section 6, PR 26)."""
    (n, din), dout = x.shape, q.shape[1]
    if not interpret:    # the interpreter knows no memory spaces
        q = pltpu.with_memory_space_constraint(q, pltpu.HBM)
    tn = _tile(dout, _TILE_N)
    tk = _tile(din, _TILE_K * max(1, _TILE_N // tn))
    nk = din // tk
    return pl.pallas_call(
        functools.partial(_gemv_kernel, n=n, nk=nk),
        grid=(dout // tn, nk),
        in_specs=[
            pl.BlockSpec((n, tk), lambda j, k: (0, k),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tk, tn), lambda j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tn), lambda j, k: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((n, tn), lambda j, k: (0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, dout), jnp.float32),
        interpret=interpret,
        name="int8_gemv",
    )(x, q, s)


def int8_gemv(x: jnp.ndarray, w: QuantW) -> jnp.ndarray:
    """y = (x @ w.q) * w.s: (N, din) f32 x QuantW(din, dout) ->
    (N, dout) f32. The int8 tile converts to bf16 (exactly) inside the
    kernel and meets x's three bf16 terms in one MXU pass (module
    docstring); the per-channel scale row multiplies the OUTPUT tile —
    constant along the contracted din, it never enters the MXU
    contraction (the absmax contract; equal to x @ dequant(w) up to one
    reassociated multiply)."""
    return _run_gemv(x.astype(jnp.float32), w.q, w.s,
                     interpret=pallas_interpret())


def qmatmul(x, w):
    """THE decode-weight matmul dispatch: plain arrays keep the `@` the
    forward always used; QuantW routes to the fused int8 GEMV. Accepts
    any leading batch shape (flattened around the kernel)."""
    if not isinstance(w, QuantW):
        return x @ w
    lead = x.shape[:-1]
    y = int8_gemv(x.reshape(-1, x.shape[-1]), w)
    return y.reshape(*lead, w.q.shape[1])


def swiglu(y, p: dict):
    """down(silu(gate y) * up y): the gated MLP, at whatever width the
    three matrices `wg`, `wu`, `wd` of `p` have (models/transformer's
    dense layers, parallel/ep's shared expert)."""
    return qmatmul(jax.nn.silu(qmatmul(y, p["wg"])) * qmatmul(y, p["wu"]),
                   p["wd"])
