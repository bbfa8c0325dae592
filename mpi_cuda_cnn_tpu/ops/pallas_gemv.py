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

The kernel tiles dout and din and keeps x resident: grid
(dout/TILE_N, din/TILE_K) with din innermost, each step one
(B, TILE_K) x (TILE_K, TILE_N) MXU contraction of the int8 tile
converted on load, accumulated into the resident f32 output tile; the
f32 scale row multiplies the finished tile on the last din step. din is
tiled because a whole-din weight block does not fit: at din = 16384
(the 4*d MLP contraction of a d = 4096 model) a (16384, 512) int8 block
is 8 MiB, 16 MiB double-buffered, against v5e's 16 MiB scoped-VMEM
limit — before the f32 convert. The dot runs at HIGHEST precision: x
is f32, and the MXU's default would round it to bf16 (the int8 weights
are exact either way). Interpret mode (platform cpu) runs the same
kernel body — the tier-1 suite pins `int8_gemv` against the jnp
dequantized form on CPU.
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


# Weight tile caps: (2048, 512) int8 is 1 MiB (2 MiB double-buffered)
# and 4 MiB once converted to f32 — inside the 16 MiB scoped-VMEM limit
# with the (N, 2048) x block and the (N, 512) output tile resident.
_TILE_N = 512
_TILE_K = 2048


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


def _gemv_kernel(x_ref, w_ref, s_ref, o_ref, *, nk):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        o_ref[:] = jnp.zeros_like(o_ref)

    o_ref[:] += jax.lax.dot_general(
        x_ref[:], w_ref[:].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(k == nk - 1)
    def _():
        o_ref[:] *= s_ref[:]


def _run_gemv(n, din, dout, operands):
    """The one pallas_call site — the MCT007 producer declared for this
    module in the lint manifest."""
    tn, tk = _tile(dout, _TILE_N), _tile(din, _TILE_K)
    nk = din // tk
    return pl.pallas_call(
        functools.partial(_gemv_kernel, nk=nk),
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
        interpret=pallas_interpret(),
        name="int8_gemv",
    )(*operands)


def int8_gemv(x: jnp.ndarray, w: QuantW) -> jnp.ndarray:
    """y = (x @ w.q) * w.s: (N, din) f32 x QuantW(din, dout) ->
    (N, dout) f32. The int8 tile converts on load inside the kernel;
    the per-channel scale row multiplies the OUTPUT tile — constant
    along the contracted din, it never enters the MXU contraction (the
    absmax contract; equal to x @ dequant(w) up to one reassociated
    multiply)."""
    n, din = x.shape
    return _run_gemv(n, din, w.q.shape[1],
                     [x.astype(jnp.float32), w.q, w.s])


def qmatmul(x, w):
    """THE decode-weight matmul dispatch: plain arrays keep the `@` the
    forward always used; QuantW routes to the fused int8 GEMV. Accepts
    any leading batch shape (flattened around the kernel)."""
    if not isinstance(w, QuantW):
        return x @ w
    lead = x.shape[:-1]
    y = int8_gemv(x.reshape(-1, x.shape[-1]), w)
    return y.reshape(*lead, w.q.shape[1])
