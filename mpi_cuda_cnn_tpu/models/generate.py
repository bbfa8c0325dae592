"""Autoregressive decoding for TransformerLM with a KV cache.

The training path (transformer.py) recomputes full-sequence attention;
reusing it per generated token would be O(S^2). This module adds the
standard cache: each block keeps (k, v) of static shape
(B, max_seq, H, D); a decode step writes position t with
dynamic_update_slice and attends over positions <= t via masking — all
static shapes, so the whole generate loop jits as one lax.scan program.

Prefill is NOT a separate forward implementation: it calls
`model.apply` with a k/v-capturing attn_fn, so the training forward stays
the single source of truth for the prompt pass (decode_step is the only
cached re-implementation, and the teacher-forcing parity test binds it to
apply()).

MoE blocks use `moe_mlp_inference` (compute-all-experts, top-k select) in
BOTH prefill and decode: exactly no-drop, O(T*E*H) memory, and token t's
output depends on token t alone — training's capacity-dropped dispatch
is a regularizer, not an inference semantic (it would leak other batch
rows' routing into a request's logits).

Sampling: greedy (temperature=0) or temperature-scaled categorical with a
jax.random key.

Two cache LAYOUTS share one decode implementation: token_forward is the
skeleton (embedding/QKV/MoE/head — QKV via transformer.project_qkv, the
same code the training forward runs) and attend_kv the masked attention
read; the contiguous max_seq buffers here and serve/paged_cache.py's
page-pool layout differ only in how cache rows are materialized.
decode_step/decode_block accept either (pass a serve.PagedKVCache with
per-slot positions for the continuous-batching form).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import NEG_INF, attention
from ..ops.pallas_gemv import qmatmul
from .transformer import TransformerLM, norm

# THE auto-dtype routing table (ISSUE 12 satellite: one place for every
# "auto" storage-dtype decision), keyed by surface -> (GQA/MQA pick,
# MHA pick). Cache row: measurement-driven (PERF.md int8 decode table,
# one v5e, 2026-07-31, the contiguous cache at d=512) — int8 wins
# +27-32% under GQA/MQA and LOSES MHA by ~9%, where bfloat16 wins
# outright. Weights row: under GQA/MQA the weight stream is the
# dominant byte mover once the cache is int8-shrunk, so int8 follows the
# same byte-dominance argument — argued, not measured: the int8 GEMV
# first compiled for the v5e in PR 21 and has no timing (ROADMAP S4
# re-derives this table); at MHA the cache dominates and the measured
# bf16-weights cast was NOT a win (PERF.md), so weights stay f32 there.
_AUTO_DTYPE_ROUTING: dict[str, tuple[str, str]] = {
    "cache": ("int8", "bfloat16"),
    "weights": ("int8", "float32"),
}


def _route_auto(surface: str, dtype: str, heads: int,
                kv_heads: int | None) -> str:
    if dtype != "auto":
        return dtype
    gqa_pick, mha_pick = _AUTO_DTYPE_ROUTING[surface]
    kv = kv_heads or heads
    return gqa_pick if kv < heads else mha_pick


def pick_cache_dtype(dtype: str, *, heads: int,
                     kv_heads: int | None = None) -> str:
    """Resolve --decode-cache-dtype "auto" to a concrete storage dtype
    (VERDICT item 7), the pick_attn_impl pattern applied to the cache:
    int8 for GQA/MQA, bfloat16 for MHA (_AUTO_DTYPE_ROUTING "cache"
    row). Explicit dtypes pass through untouched — "auto" is a router,
    not a cap, exactly like pick_attn_impl's contract."""
    return _route_auto("cache", dtype, heads, kv_heads)


def pick_weights_dtype(dtype: str, *, heads: int,
                       kv_heads: int | None = None) -> str:
    """Resolve --decode-weights-dtype "auto" (ISSUE 12): int8 for
    GQA/MQA — where the weight stream dominates the decode bytes once
    the cache is int8 — float32 for MHA, where the cache dominates and
    the measured bf16 weights cast was not a win (_AUTO_DTYPE_ROUTING
    "weights" row; same pass-through contract as pick_cache_dtype)."""
    return _route_auto("weights", dtype, heads, kv_heads)


def init_cache(model: TransformerLM, batch: int,
               dtype=jnp.float32) -> list[dict]:
    """Empty per-block KV buffers, static (B, max_seq, Hkv, head_dim) —
    under GQA the cache shrinks by heads/kv_heads (the reason serving
    stacks use GQA: cache bytes bound decode batch size). `dtype`
    bfloat16 halves the cache again: decode is cache-READ-bound (PERF.md
    decode table — tokens/s tracks cache bytes almost linearly), so the
    storage dtype is a bandwidth lever independent of GQA; scores and
    softmax stay f32 either way (decode_block accumulates in f32).

    `dtype` int8 is the next factor-2: k/v quantize per (position, head)
    — absmax/127 scales stored alongside as f32 (B, S, Hkv, 1): +4
    bytes per 512-byte f32 row at head_dim 128 (0.8% of the f32 cache's
    bytes; ~3% of the int8 cache's). The scales never enter the MXU
    contractions: a k-row's scale is constant along the contracted
    head_dim, so it multiplies the LOGITS after the QK dot, and a
    v-row's scale folds into the probabilities before the PV dot. The
    STORED cache is pure int8 (the bandwidth lever); decode_block's
    einsums consume it through an int8->f32 convert, whose cost shows
    at the MHA shape (PERF.md round-5 decode table: int8 wins +27-32%
    at GQA/MQA, loses ~9% at MHA where the convert spans 8x the
    bytes)."""
    if model.attn is not None:
        raise ValueError(
            "the contiguous cache holds K/V heads; a model with latent "
            "attention is served from the paged cache (serve.paged_cache)")
    shape = (batch, model.max_seq, model.n_kv, model.head_dim)
    if jnp.dtype(dtype) == jnp.int8:
        sshape = shape[:-1] + (1,)
        return [
            {"k": jnp.zeros(shape, jnp.int8),
             "ks": jnp.zeros(sshape, jnp.float32),
             "v": jnp.zeros(shape, jnp.int8),
             "vs": jnp.zeros(sshape, jnp.float32)}
            for _ in range(model.depth)
        ]
    return [
        {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
        for _ in range(model.depth)
    ]


def _quant_kv(x):
    """Per-(batch, position, head) absmax int8 quantization of a
    (B, T, Hkv, head_dim) k/v tensor: returns (int8 values, f32 scales
    (B, T, Hkv, 1)) with x ≈ values * scales."""
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-10)
    q = jnp.clip(jnp.round(xf / s), -127, 127).astype(jnp.int8)
    return q, s


def prefill(model: TransformerLM, params, prompt: jnp.ndarray,
            cache_dtype=jnp.float32):
    """Batched prompt pass: ONE model.apply call whose attn_fn captures
    each block's K/V into max_seq-sized cache buffers (stored as
    `cache_dtype`; the prompt pass itself still attends at full
    precision — only the cache the DECODE steps read is quantized).

    Returns (logits_last: (B, vocab), cache).
    """
    b, s0 = prompt.shape
    if s0 > model.max_seq:
        raise ValueError(f"prompt length {s0} exceeds max_seq {model.max_seq}")
    full = (b, model.max_seq, model.n_kv, model.head_dim)
    sfull = full[:-1] + (1,)
    int8 = jnp.dtype(cache_dtype) == jnp.int8
    cache: list[dict] = []

    def capture_attn(q, k, v):
        if int8:
            qk, sk = _quant_kv(k)
            qv, sv = _quant_kv(v)
            cache.append({
                "k": lax.dynamic_update_slice(
                    jnp.zeros(full, jnp.int8), qk, (0, 0, 0, 0)
                ),
                "ks": lax.dynamic_update_slice(
                    jnp.zeros(sfull, jnp.float32), sk, (0, 0, 0, 0)
                ),
                "v": lax.dynamic_update_slice(
                    jnp.zeros(full, jnp.int8), qv, (0, 0, 0, 0)
                ),
                "vs": lax.dynamic_update_slice(
                    jnp.zeros(sfull, jnp.float32), sv, (0, 0, 0, 0)
                ),
            })
        else:
            cache.append({
                "k": lax.dynamic_update_slice(
                    jnp.zeros(full, cache_dtype), k.astype(cache_dtype),
                    (0, 0, 0, 0),
                ),
                "v": lax.dynamic_update_slice(
                    jnp.zeros(full, cache_dtype), v.astype(cache_dtype),
                    (0, 0, 0, 0),
                ),
            })
        return attention(q, k, v, causal=True)

    logits = model.apply(
        params, prompt, attn_fn=capture_attn, moe_inference=True
    )
    # f32 logits regardless of the weights dtype (bf16 serving weights
    # would otherwise produce bf16 logits here and f32 in decode_step —
    # the generate scan carries logits, so the two must agree).
    return logits[:, -1, :].astype(jnp.float32), cache


def decode_step(model: TransformerLM, params, tok, pos, cache):
    """One token through the model using/updating the cache — the k=1
    case of decode_block (one forward implementation; the speculative
    path's greedy-exactness depends on the two never drifting).

    tok: (B,) int32 current tokens; pos: their position — a traced scalar
    inside generate()'s scan (bounds are enforced there; a concrete
    out-of-range pos raises here, a traced one cannot be checked).
    Returns (logits: (B, vocab), new_cache).
    """
    logits, new_cache = decode_block(model, params, tok[:, None], pos, cache)
    return logits[:, 0, :], new_cache


def token_forward(model: TransformerLM, params, toks, positions, attend,
                  valid=None):
    """THE cached-decode forward skeleton: k tokens per row at explicit
    absolute positions, with the attention/cache behavior injected per
    layer. Everything around attention — embedding, norms, QKV
    projections + rotary (transformer.project_qkv, shared with the
    training forward), the feed-forward, final head — has exactly one
    implementation; the contiguous decode_block and serve/'s paged
    continuous-batching path differ ONLY in their `attend`.

    The block is data: what a layer computes is read off its params
    (transformer.norm: a gain alone is RMSNorm; TransformerLM.mlp: GELU
    `w1`/`w2`, gated `wg`/`wu`/`wd` at any width, or an `experts` bank;
    `pos_emb` or none; `q_norm`/`k_norm` gains over a head's dims;
    `o_norm`, a gain over each head's dims of the mixer's output;
    `wgate`, an elementwise sigmoid gate on it read from the layer's
    normed input) and off the model where the params cannot say
    (`attn`: K/V heads or one latent row; `layout`: which layers
    rotate; `mixers`: which keep a state instead of keys; `experts`:
    the router's kind and where it reads; the muP scales), so layers
    of different kinds ride one loop.

    toks: (B, k) int32; positions: (k,) shared across rows, or (B, k)
    PER-ROW absolute positions (the serving form — each slot sits at
    its own depth). attend(i, q, k, v) -> (B, k, H*hd) f32 performs
    layer i's cache update + masked attention read (closing over its
    cache; layers are traced in order, so append-style capture works —
    the same idiom as prefill's attn_fn). valid: (B, k) bool, the rows
    that are tokens of a request (None = all); only an expert layer
    reads it, to route nothing else.

    Every weight matmul routes through ops.pallas_gemv.qmatmul, so
    params may carry int8 QuantW leaves (quantize_decode_params,
    --decode-weights-dtype int8) — the decode-weight bandwidth lever
    rides the SAME forward, not a second one.
    Returns ((B, k, vocab) f32 logits, counts): counts the expert
    layers' int32 [pairs computed (summed), held experts hit (summed),
    largest load (max)], None for a model without any.
    """
    x = params["tok_emb"][toks]                           # (B, k, dim)
    if model.emb_scale != 1.0:
        x = x * model.emb_scale
    if model.pos == "learned":
        # (k, dim) broadcasts over rows; (B, k, dim) indexes per row.
        x = x + params["pos_emb"][positions]
    eps, counts = model.norm_eps, None
    # A residual branch's scale (muP's depth scaling), where there is one.
    branch = ((lambda m: m) if model.residual_scale == 1.0
              else (lambda m: m * model.residual_scale))
    for i, blk in enumerate(params["blocks"]):
        # A router that reads the layer's input chooses here, before
        # attention; its experts run on the stream after it.
        routing = model.route(blk, x)
        y = norm(x, blk["ln1"], eps)
        q, k, v = model.project_qkv(blk, y, positions=positions, layer=i)
        o = attend(i, q, k, v)
        if "o_norm" in blk:     # RMSNorm over each head's dims, a gain
            o = norm(o.reshape(*o.shape[:2], model.heads, -1),
                     blk["o_norm"], eps).reshape(o.shape)
        if "wgate" in blk:      # the output gate reads the layer's
            o = o * jax.nn.sigmoid(     # normed input, elementwise
                qmatmul(y, blk["wgate"]).astype(jnp.float32))
        x = x + branch(qmatmul(o.astype(x.dtype), blk["wo"]))
        m, c = model.mlp(blk, norm(x, blk["ln2"], eps), valid, routing)
        x = x + branch(m)
        if c is not None:
            counts = c if counts is None else jnp.concatenate(
                [counts[:2] + c[:2], jnp.maximum(counts[2:], c[2:])])
    x = norm(x, params["ln_f"], eps)
    if model.logit_scale != 1.0:
        x = x * model.logit_scale
    return qmatmul(x, params["head"]).astype(jnp.float32), counts


def causal_mask(keys, queries, window: int = 0):
    """True where the key at position `keys` is seen by the query at
    position `queries` (broadcast against each other): keys <= queries,
    and under a sliding window of `window` keys, the query itself
    included, also keys > queries - window."""
    seen = keys <= queries
    if window:
        seen = seen & (keys > queries - window)
    return seen


def attend_kv(q, ck, cv, mask, cks=None, cvs=None):
    """THE masked GQA attention read over materialized cache rows — the
    one implementation both cache layouts consume (the contiguous
    max_seq buffers here, the paged gather in serve/paged_cache.py; the
    paged-vs-contiguous bitwise parity rests on this being shared).

    q: (B, k, H, hd); ck/cv: (B, L, Hkv, hd) cache rows (any storage
    dtype; int8 rows come with cks/cvs absmax scales (B, L, Hkv, 1),
    applied OUTSIDE the dots — a key row's scale is constant along the
    contracted head_dim so it factors onto the logits, a value row's
    folds into the probabilities before the PV contraction). mask:
    (k, L) or (B, k, L) bool, True = attend, or (B, Hkv, k, L) where
    each K/V head's queries see keys of their own (block selection);
    scores/softmax are f32.
    Returns (B, k, H*hd) f32.
    """
    b, kk, h, hd = q.shape
    hkv = ck.shape[2]
    int8 = ck.dtype == jnp.int8
    g = h // hkv
    qg = q.reshape(b, kk, hkv, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg,
        ck.astype(jnp.float32) if int8 else ck,
        preferred_element_type=jnp.float32,
    ) * scale                                 # (B, Hkv, g, k, L)
    if int8:
        logits = logits * jnp.transpose(cks, (0, 2, 3, 1))[:, :, None, :, :]
    if mask.ndim == 2:
        mask = mask[None]                     # shared across rows
    if mask.ndim == 3:
        mask = mask[:, None, None, :, :]      # ... and across heads
    else:                                     # (B, Hkv, k, L): a K/V
        mask = mask[:, :, None, :, :]         # head's own (selection)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if int8:
        pv = probs * jnp.transpose(cvs, (0, 2, 3, 1))[:, :, None, :, :]
        o = jnp.einsum(
            "bhgqk,bkhd->bqhgd", pv, cv.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    else:
        o = jnp.einsum(
            "bhgqk,bkhd->bqhgd", probs.astype(cv.dtype), cv,
            preferred_element_type=jnp.float32,
        )
    return o.reshape(b, kk, h * hd)


def latent_query_rows(q, wuk, a, like):
    """The absorbed query of every head, laid out as the cache rows
    `like` (..., stored lanes) lie: q~_h = wuk_h q_n,h in the weights'
    type, then [q~_h ; q_r,h ; zero lanes] in the rows' type, heads and
    queries as ONE axis of H*k rows (head-major) -- every head reads
    the same cache row, whole. q (B, k, H, nope + rope) ->
    (B, H*k, stored lanes). Once per query: attend_latent and the paged
    cache's bounded latent read both start here."""
    b, kk, h, _ = q.shape
    # q~ in the weights' type: the rows' type is where it is read.
    # Head-major from the start: transposing the finished rows is a
    # copy of all of them (30 us a layer at the benchmark's tick).
    qt = jnp.einsum("bqhn,hnr->bhqr", q[..., :a.nope].astype(wuk.dtype), wuk)
    qrow = jnp.concatenate(
        [qt.astype(like.dtype),
         q[..., a.nope:].transpose(0, 2, 1, 3).astype(like.dtype),
         jnp.zeros((b, h, kk, like.shape[-1] - a.row), like.dtype)],
        axis=-1)                                # (B, H, k, stored row)
    return qrow.reshape(b, h * kk, -1)


def latent_values_up(ot, wuv):
    """The other end of the absorbed read: ot (B, H, k, kv_rank), the
    softmax-weighted latents of every head and query, up-projected by
    wuv (H, kv_rank, v) once per QUERY. Returns (B, k, H*v) f32."""
    b, h, kk, _ = ot.shape
    o = jnp.einsum("bhqr,hrv->bqhv", ot.astype(wuv.dtype), wuv).astype(
        jnp.float32)
    return o.reshape(b, kk, h * wuv.shape[-1])


def attend_latent(q, rows, mask, wuk, wuv, a):
    """The masked read over LATENT cache rows (transformer.LatentAttn):
    rows (B, L, >= kv_rank + rope) hold, a token, the compressed latent
    c, the one rotated key k_r all heads share, and zero lanes after
    them where the pool pads a row; q (B, k, H, nope +
    rope), rotated part last; wuk (H, nope, kv_rank), wuv (H, kv_rank,
    v) the per-head up-projections of keys and values, head first: a
    batch of small matrices as they lie.

    The ABSORBED form: q~_h = wuk_h q_n,h reads the rows as they lie,
    score = q~_h . c + q_r,h . k_r, and the values are the rows' c
    again, up-projected once per QUERY. It equals up-projecting every
    row to per-head keys and values first (the materialized form, which
    the benchmark's reference computes and tests/test_mla_moe.py holds
    this against) up to rounding, at far fewer operations while a
    slot's queries are few: a decode tick has one, a prefill chunk 32,
    and the two cross near 150 (batched prefill, ROADMAP S2, brings the
    other form back with a trace of both). mask: (k, L) or (B, k, L),
    True = attend; scores and softmax f32. Returns (B, k, H*v) f32.

    This is the read over WHOLE rows in one softmax: the paged cache
    calls it where a block table is small enough to gather whole
    (serve/paged_cache.bounded_read_latent: the tiny presets, the
    tests' tables); a large table is read block by block there, with
    these same products (latent_query_rows, latent_values_up) around
    an online softmax."""
    b, kk, h, _ = q.shape
    f32 = dict(preferred_element_type=jnp.float32)
    c = rows[..., :a.kv_rank]
    if mask.ndim == 2:
        mask = mask[None]
    qrow = latent_query_rows(q, wuk, a, rows)
    logits = jnp.einsum("bmc,bkc->bmk", qrow, rows, **f32).reshape(
        b, h, kk, -1)
    logits = jnp.where(mask[:, None], logits * a.softmax_scale, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(rows.dtype)
    ot = jnp.einsum("bmk,bkr->bmr", probs.reshape(b, h * kk, -1), c,
                    **f32).reshape(b, h, kk, a.kv_rank)
    return latent_values_up(ot, wuv)


def linear_attend(q, k, v, state, valid, log_decay):
    """Lightning attention (transformer.LinearAttn) over kk rows a
    batch row, from the state the rows before them left: q, k, v
    (B, kk, H, hd); state (B, H, hd, hd) f32, S = sum over earlier rows
    of l^(rows since) k^T v; valid (B, kk) bool; log_decay (H,) = log l,
    negative. With c_i the valid rows up to and including i:

      o_i = (sum over valid j <= i of l^(c_i - c_j) (q_i . k_j) v_j
             + l^(c_i) q_i S) / sqrt(hd)
      S'  = l^(c_last) S + sum over valid j of l^(c_last - c_j) k_j^T v_j

    -- the token recurrence S_t = l S_{t-1} + k_t^T v_t, o_t = q_t S_t
    unrolled over the rows, so a tick's one row and a prefill chunk's
    hundreds are one form. A row that is not valid adds no k^T v and
    applies no decay (its own output is nobody's). Every exponent is
    <= 0: nothing is divided by a decay, so a head that forgets within
    a few rows underflows to 0 and never overflows. The (kk, kk)
    products run in the inputs' type with f32 accumulation, as
    attend_kv's do; the state is read and updated in f32 at HIGHEST (a
    (hd, hd) product a head: nothing beside the rest).
    Returns (o (B, kk, H*hd) f32, the new state)."""
    b, kk, h, hd = q.shape
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    c = jnp.cumsum(valid, axis=1).astype(f32)                  # (B, kk)
    ld = log_decay.astype(f32)
    at = jnp.arange(kk)
    seen = (valid[:, :, None] & valid[:, None, :]
            & (at[:, None] >= at[None, :]))                    # (B, q, k)
    d = jnp.where(seen[:, None], jnp.exp(
        ld[None, :, None, None] * (c[:, :, None] - c[:, None, :])[:, None]),
        0.0)                                                   # (B, H, q, k)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=f32) * d
    o = jnp.einsum("bhqk,bkhd->bqhd", s.astype(v.dtype), v,
                   preferred_element_type=f32)
    carried = jnp.einsum("bqhd,bhde->bqhe", q.astype(f32), state,
                         precision=hi)
    o = o + carried * jnp.exp(ld[None, None, :] * c[:, :, None])[..., None]
    n = c[:, -1]                                               # (B,)
    w = jnp.where(valid[..., None], jnp.exp(
        ld[None, None, :] * (n[:, None] - c)[..., None]), 0.0)  # (B, kk, H)
    new = (jnp.exp(ld[None, :] * n[:, None])[..., None, None] * state
           + jnp.einsum("bkhd,bkhe->bhde", k.astype(f32) * w[..., None],
                        v.astype(f32), precision=hi))
    return (o / jnp.sqrt(jnp.asarray(hd, f32))).reshape(b, kk, h * hd), new


def attend_contiguous(c, q, k, v, pos, positions, window: int = 0):
    """Contiguous-cache attend: write k/v at [pos, pos+k) of the static
    (B, max_seq, Hkv, hd) buffers, then attend each row i over keys at
    positions <= positions[i] (attend_kv does the masked read); a
    windowed layer's `window` is a mask here and nothing else.
    Returns (o: (B, k, H*hd) f32, new_c)."""
    int8 = c["k"].dtype == jnp.int8
    if int8:
        qk8, sk8 = _quant_kv(k)
        qv8, sv8 = _quant_kv(v)
        new_c = {
            "k": lax.dynamic_update_slice(c["k"], qk8, (0, pos, 0, 0)),
            "ks": lax.dynamic_update_slice(c["ks"], sk8, (0, pos, 0, 0)),
            "v": lax.dynamic_update_slice(c["v"], qv8, (0, pos, 0, 0)),
            "vs": lax.dynamic_update_slice(c["vs"], sv8, (0, pos, 0, 0)),
        }
    else:
        new_c = {
            "k": lax.dynamic_update_slice(c["k"], k.astype(c["k"].dtype),
                                          (0, pos, 0, 0)),
            "v": lax.dynamic_update_slice(c["v"], v.astype(c["v"].dtype),
                                          (0, pos, 0, 0)),
        }
    # Rows attend over the cached prefix + the block's causal part:
    # row i sees keys at positions <= pos+i.
    mask = causal_mask(jnp.arange(new_c["k"].shape[1])[None, :],
                       positions[:, None], window)       # (k, max_seq)
    o = attend_kv(q, new_c["k"], new_c["v"], mask,
                  cks=new_c.get("ks"), cvs=new_c.get("vs"))
    return o, new_c


def decode_block(model: TransformerLM, params, toks, pos, cache):
    """k tokens through the model at positions [pos, pos+k): the block
    form of decode_step, for speculative verification — ONE forward
    scores k candidate tokens instead of k sequential decode steps.

    toks: (B, k) int32; pos: start position (traced scalar OK; a
    concrete out-of-range block raises here — dynamic_update_slice
    would otherwise clamp the write start while positions/RoPE/mask use
    the unclamped pos, silently corrupting the cache). Writes all k
    cache slots FIRST, then attends each row i over keys <= pos+i — so
    within-block causality holds and any stale entries beyond the
    accepted prefix from a previous speculative round are either
    overwritten here or masked by the row bound.

    `cache` may also be a serve.paged_cache.PagedKVCache (pos then may
    be a (B,) per-slot vector) — the decode surface accepts either
    cache layout. Detection is by the block_table attribute, so the
    serve package only loads when a paged cache is actually passed
    (models/ must not depend on serve/ — serve/ imports THIS module).
    Returns (logits: (B, k, vocab), new_cache).
    """
    if hasattr(cache[0] if isinstance(cache, tuple) else cache,
               "block_table"):       # one PagedKVCache, or one a group
        from ..serve.paged_cache import paged_decode_block

        return paged_decode_block(model, params, toks, pos, cache)
    b, kk = toks.shape
    if isinstance(pos, int) and pos + kk > model.max_seq:
        raise ValueError(
            f"block [{pos}, {pos + kk}) out of range (max_seq "
            f"{model.max_seq})"
        )
    positions = pos + jnp.arange(kk)
    new_cache = []

    def attend(i, q, k, v):
        o, new_c = attend_contiguous(cache[i], q, k, v, pos, positions,
                                     model.layer_window(i))
        new_cache.append(new_c)
        return o

    logits, _ = token_forward(model, params, toks, positions, attend)
    return logits, new_cache


def filter_logits(logits, top_k: int = 0, top_p: float = 0.0):
    """Top-k / nucleus (top-p) restriction: logits outside the kept set
    go to NEG_INF. top_k keeps the k largest (ties at the boundary all
    survive — the standard threshold form; values above the vocab size
    clamp to it — keeping everything — instead of indexing out of
    range); top_p keeps the smallest prefix of the probability-sorted
    vocabulary whose mass reaches p. Both may combine; 0 disables
    either. Pure and shape-preserving, so it composes with
    jax.random.categorical and jits inside the decode scan."""
    l = logits.astype(jnp.float32)
    if top_k:
        thr = jnp.sort(l, axis=-1)[..., -min(top_k, l.shape[-1]), None]
        l = jnp.where(l >= thr, l, NEG_INF)
    if top_p:
        sorted_l = jnp.sort(l, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        # Mass BEFORE each token: tokens whose preceding cumulative mass
        # already reaches p are cut; the boundary token stays (the set
        # must reach p, not stop short of it).
        cum_before = jnp.cumsum(probs, axis=-1) - probs
        kept = cum_before < top_p
        cutoff = jnp.min(
            jnp.where(kept, sorted_l, jnp.inf), axis=-1, keepdims=True
        )
        l = jnp.where(l >= cutoff, l, NEG_INF)
    return l


@functools.lru_cache(maxsize=64)
def _compiled_run(model: TransformerLM, s0: int, num_tokens: int,
                  temperature: float, cache_dtype: str,
                  top_k: int, top_p: float):
    """One jitted prefill+scan program per (model, shape, sampling,
    cache dtype) combination — repeat generate() calls hit this cache
    instead of retracing."""
    cdt = jnp.dtype(cache_dtype)

    def sample(logits, k):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # Temperature FIRST, then the nucleus: the kept set must be
        # computed on the distribution actually sampled (top_p on the
        # flattened T>1 distribution keeps more tokens — the standard
        # semantics; top_k is temperature-invariant either way).
        l = filter_logits(logits.astype(jnp.float32) / temperature,
                          top_k, top_p)
        return jax.random.categorical(k, l, axis=-1).astype(jnp.int32)

    def gen_body(params):
        def body(carry, i):
            cache, logits, klocal = carry
            klocal, kstep = jax.random.split(klocal)
            tok = sample(logits, kstep)
            logits, cache = decode_step(model, params, tok, s0 + i, cache)
            return (cache, logits, klocal), tok

        return body

    @jax.jit
    def run(params, prompt, key):
        logits, cache = prefill(model, params, prompt, cache_dtype=cdt)
        # Scan N-1 steps (each samples from the carried logits, then runs
        # the forward that produces the NEXT logits); the final token only
        # needs a sample, not another forward.
        (_, logits, key), toks = lax.scan(
            gen_body(params), (cache, logits, key),
            jnp.arange(num_tokens - 1),
        )
        key, klast = jax.random.split(key)
        last = sample(logits, klast)
        return jnp.concatenate([toks, last[None, :]], axis=0).T

    return run


def _emit_rows(y, accept, out, n_out):
    """Buffered emit shared by the greedy and sampling acceptance paths:
    y (1, k) emit rows, accept (k-1,) bool prefix flags. j = 1 + the
    accepted prefix length (row j-1 is the first-reject replacement or
    the bonus row); ALL k rows are written at n_out — rows beyond j are
    rewritten by the next round's write. Returns (j, new cur, out)."""
    j = 1 + jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))
    out = lax.dynamic_update_slice(out, y, (0, n_out))
    cur = lax.dynamic_slice(y, (0, j - 1), (1, 1))[:, 0]
    return j, cur, out


def _accept_and_emit(u, y, out, n_out):
    """The GREEDY speculative acceptance core, shared by the model-draft
    and prompt-lookup runners so the two can never drift: u (1, k)
    verify inputs, y (1, k) target argmax picks. Accept the longest
    prefix where input i+1 equals the target's pick at row i (j in
    [1, k] tokens emitted per round). The serving engine's batched
    form (ISSUE 14) applies the SAME law host-side per slot —
    serve/spec.accept_len — and a randomized equivalence test
    (tests/test_spec_serve.py) pins the two dialects against drift."""
    matches = u[0, 1:] == y[0, :-1]
    return _emit_rows(y, matches, out, n_out)


def _filtered_probs(logits, temperature, top_k, top_p):
    """f32 probabilities of temperature-scaled, top-k/top-p-restricted
    logits — the distribution `generate()` actually samples from; the
    speculative sampling paths must target exactly this law."""
    l = filter_logits(logits.astype(jnp.float32) / temperature, top_k, top_p)
    return jax.nn.softmax(l, axis=-1)


def _spec_sample_rows(tl, qs, u, key, temperature, top_k, top_p):
    """Rejection-sampling acceptance for one verify block (B=1) — the
    T>0 analog of _accept_and_emit's matching, implementing the standard
    speculative sampling theorem (accept draft token x w.p.
    min(1, p(x)/q(x)); replace a reject with a sample from the residual
    norm(max(p-q, 0)); after a fully accepted chain, sample the bonus
    row from p directly). The emitted token at every row is then
    distributed EXACTLY as p for ANY proposal law q — the draft moves
    the speed, never the law (tests/test_spec_sampling.py pins this
    against analytic distributions).

    tl: (1, k, V) target logits — row i is the target's distribution
        for the token following verify input u[:, i];
    qs: (k-1, V) f32 draft probabilities — row i is the law proposal
        u[:, i+1] was drawn from (a one-hot delta for prompt-lookup);
    u:  (1, k) int32 verify inputs (u[:, 0] is already emitted).
    Returns (y: (1, k) int32 emit rows, accept: (k-1,) bool).
    """
    kk = tl.shape[1]
    p = _filtered_probs(tl[0], temperature, top_k, top_p)      # (k, V)
    props = u[0, 1:]                                           # (k-1,)
    ku, kr, kb = jax.random.split(key, 3)
    p_prop = jnp.take_along_axis(p[:-1], props[:, None], axis=-1)[:, 0]
    q_prop = jnp.take_along_axis(qs, props[:, None], axis=-1)[:, 0]
    # u*q < p  <=>  u < min(1, p/q) (u < 1 surely); q = 0 accepts iff
    # p > 0 — a proposal the target filters out (p = 0) always rejects.
    unif = jax.random.uniform(ku, (kk - 1,))
    accept = unif * q_prop < p_prop
    # Residual for each non-bonus row: norm(max(p - q, 0)). A row can be
    # identically zero two ways: p == q exactly (never selected —
    # acceptance there is 1, the sample unused) or p <= q everywhere by
    # ROUNDING while p < q at the proposal (rejection still possible,
    # and categorical over an all -inf row would deterministically emit
    # token 0, even one with p = 0). Guard the degenerate row by
    # falling back to sampling from p itself — within the same rounding
    # band that zeroed the residual, so the output law stays exact to
    # float precision (ADVICE round 5).
    res = jnp.maximum(p[:-1] - qs, 0.0)
    res = jnp.where(
        jnp.sum(res, axis=-1, keepdims=True) > 0.0, res, p[:-1]
    )
    res_tok = jax.random.categorical(kr, jnp.log(res), axis=-1)
    bonus = jax.random.categorical(kb, jnp.log(p[-1]))
    y_head = jnp.where(accept, props, res_tok.astype(jnp.int32))
    y = jnp.concatenate([y_head, bonus[None].astype(jnp.int32)])
    return y[None, :], accept


@functools.lru_cache(maxsize=16)
def _compiled_spec_run(model: TransformerLM, draft: TransformerLM,
                       s0: int, num_tokens: int, k: int, cache_dtype: str,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 0.0):
    """Jitted speculative loop for one (models, shapes, sampling) combo:
    greedy exact-match acceptance at temperature 0, rejection sampling
    (draft samples its own filtered law; _spec_sample_rows targets the
    filtered target law) at temperature > 0."""
    cdt = jnp.dtype(cache_dtype)
    sampling = temperature > 0

    @jax.jit
    def run(params, draft_params, prompt, key):
        tl, t_cache = prefill(model, params, prompt, cache_dtype=cdt)
        dl, d_cache = prefill(draft, draft_params, prompt, cache_dtype=cdt)
        del dl  # the draft's prompt logits are not used: the first
        #         generated token is the TARGET's own pick/sample
        if sampling:
            key, k0 = jax.random.split(key)
            cur = jax.random.categorical(
                k0, jnp.log(_filtered_probs(tl, temperature, top_k, top_p))
            ).astype(jnp.int32)                               # (1,)
        else:
            cur = jnp.argmax(tl, axis=-1).astype(jnp.int32)   # (1,)
        out = jnp.zeros((1, num_tokens + k), jnp.int32)
        out = lax.dynamic_update_slice(out, cur[:, None], (0, 0))

        def draft_step(carry, _):
            tok, pos, dc, kd = carry
            logits, dc = decode_step(draft, draft_params, tok, pos, dc)
            if sampling:
                q = _filtered_probs(logits, temperature, top_k, top_p)
                kd, ks = jax.random.split(kd)
                nxt = jax.random.categorical(
                    ks, jnp.log(q)
                ).astype(jnp.int32)
            else:
                q = jnp.zeros_like(logits)        # unused in greedy mode
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, pos + 1, dc, kd), (nxt, q[0])

        def round_body(state):
            pos, cur, t_cache, d_cache, out, n_out, rounds, key = state
            # 1. Draft k sequential steps, INGESTING each fed token so
            #    its cache stays aligned with the verified prefix; the
            #    last proposal is never fed anywhere (d_k is unused).
            key, kd, kv = jax.random.split(key, 3)
            (_, _, d_cache, _), (ds, qs) = lax.scan(
                draft_step, (cur, pos, d_cache, kd), None, length=k
            )                     # ds: (k, 1) proposals; qs: (k, V) laws
            u = jnp.concatenate([cur[None, :], ds[: k - 1, :]],
                                axis=0).T         # (1, k) verify inputs
            # 2. One target block forward scores all k inputs.
            tl, t_cache = decode_block(model, params, u, pos, t_cache)
            # 3./4. Acceptance + buffered emit — exact-match (greedy) or
            #    rejection-sampling (_spec_sample_rows), same emit core.
            if sampling:
                y, accept = _spec_sample_rows(
                    tl, qs[: k - 1], u, kv, temperature, top_k, top_p
                )
                j, cur, out = _emit_rows(y, accept, out, n_out)
            else:
                y = jnp.argmax(tl, axis=-1).astype(jnp.int32)  # (1, k)
                j, cur, out = _accept_and_emit(u, y, out, n_out)
            return (pos + j, cur, t_cache, d_cache, out, n_out + j,
                    rounds + 1, key)

        def cond(state):
            return state[5] < num_tokens

        state = (jnp.asarray(s0), cur, t_cache, d_cache, out,
                 jnp.asarray(1), jnp.asarray(0), key)
        pos, cur, _, _, out, n_out, rounds, _ = lax.while_loop(
            cond, round_body, state
        )
        return out[:, :num_tokens], n_out, rounds

    return run


@functools.lru_cache(maxsize=16)
def _compiled_lookup_run(model: TransformerLM, s0: int, num_tokens: int,
                         k: int, ngram: int, cache_dtype: str,
                         temperature: float = 0.0, top_k: int = 0,
                         top_p: float = 0.0):
    """Jitted prompt-lookup speculative loop (draft-free). At
    temperature > 0 the deterministic proposal is a one-hot law, so
    rejection sampling degenerates to: accept proposal x w.p. p(x),
    resample from p-with-x-zeroed on reject — still exactly p."""
    cdt = jnp.dtype(cache_dtype)
    L = model.max_seq
    V = model.vocab
    sampling = temperature > 0

    @jax.jit
    def run(params, prompt, key):
        tl, t_cache = prefill(model, params, prompt, cache_dtype=cdt)
        if sampling:
            key, k0 = jax.random.split(key)
            cur = jax.random.categorical(
                k0, jnp.log(_filtered_probs(tl, temperature, top_k, top_p))
            ).astype(jnp.int32)                               # (1,)
        else:
            cur = jnp.argmax(tl, axis=-1).astype(jnp.int32)   # (1,)
        ctx = jnp.zeros((1, L), jnp.int32)
        ctx = lax.dynamic_update_slice(ctx, prompt, (0, 0))
        ctx = lax.dynamic_update_slice(ctx, cur[:, None], (0, s0))
        out = jnp.zeros((1, num_tokens + k), jnp.int32)
        out = lax.dynamic_update_slice(out, cur[:, None], (0, 0))

        def propose(ctx, pos, cur):
            """The k-1 tokens that followed the MOST RECENT earlier
            occurrence of the context's current ngram-token tail
            (ctx[pos] == cur is already written). No match -> repeat
            cur: acceptance just collapses to 1, never an error. When
            the match sits within k-1 of the buffer end, the window
            start clamps to L-(k-1): the proposals then trail the
            clamped window (not the match) — acceptance drops, the
            contract (tokens come from ctx) holds."""
            idx = jnp.arange(L)
            match = (idx >= ngram - 1) & (idx < pos)
            row = ctx[0]
            for d in range(ngram):
                # row[j-d] vs row[pos-d]; jnp.roll wraps for j < d but
                # those rows are outside the idx >= ngram-1 window.
                match &= jnp.roll(row, d) == row[pos - d]
            j = jnp.max(jnp.where(match, idx, -1))
            start = jnp.clip(j + 1, 0, L - (k - 1))
            props = lax.dynamic_slice(ctx, (0, start), (1, k - 1))[0]
            return jnp.where(j >= 0, props,
                             jnp.broadcast_to(cur, (k - 1,)))

        def round_body(state):
            pos, cur, t_cache, ctx, out, n_out, rounds, key = state
            props = propose(ctx, pos, cur)
            u = jnp.concatenate([cur, props])[None, :]        # (1, k)
            tl, t_cache = decode_block(model, params, u, pos, t_cache)
            if sampling:
                key, kv = jax.random.split(key)
                qs = jax.nn.one_hot(props, V, dtype=jnp.float32)
                y, accept = _spec_sample_rows(
                    tl, qs, u, kv, temperature, top_k, top_p
                )
                j, cur, out = _emit_rows(y, accept, out, n_out)
            else:
                y = jnp.argmax(tl, axis=-1).astype(jnp.int32)
                j, cur, out = _accept_and_emit(u, y, out, n_out)
            # Keep the context buffer current: the accepted picks land
            # at pos+1.. (rows beyond j overwritten next round, same
            # trick as `out`; ctx[pos+j] == new cur by construction).
            ctx = lax.dynamic_update_slice(ctx, y, (0, pos + 1))
            return (pos + j, cur, t_cache, ctx, out, n_out + j,
                    rounds + 1, key)

        def cond(state):
            return state[5] < num_tokens

        state = (jnp.asarray(s0), cur, t_cache, ctx, out,
                 jnp.asarray(1), jnp.asarray(0), key)
        pos, cur, _, _, out, n_out, rounds, _ = lax.while_loop(
            cond, round_body, state
        )
        return out[:, :num_tokens], n_out, rounds

    return run


def _validate_spec_sampling(temperature, key, top_k, top_p, vocab):
    """Shared sampling-argument validation for the speculative paths —
    the same contract generate() enforces."""
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if top_k < 0 or top_k > vocab:
        raise ValueError(f"top_k {top_k} not in [0, vocab {vocab}]")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p {top_p} not in [0, 1]")
    if (top_k or top_p) and temperature <= 0:
        raise ValueError(
            "top_k/top_p restrict SAMPLING — set temperature > 0 "
            "(greedy argmax already takes the single most likely token)"
        )


def _spec_stats(n_out, rounds, num_tokens):
    """Acceptance stats with the emitted count CAPPED at num_tokens: the
    final round may overshoot the budget by up to k-1 accepted tokens
    that never land in the returned buffer — counting them would inflate
    the rate (round-4 advisor finding)."""
    r = max(int(rounds), 1)
    return {"rounds": int(rounds),
            "mean_accepted": (min(int(n_out), num_tokens) - 1) / r}


def lookup_speculative_generate(
    model: TransformerLM,
    params,
    prompt: jnp.ndarray,          # (1, S0) int32 — latency path, B = 1
    num_tokens: int,
    *,
    k: int = 8,
    ngram: int = 2,
    cache_dtype="float32",
    temperature: float = 0.0,
    key: jax.Array | None = None,
    top_k: int = 0,
    top_p: float = 0.0,
    return_stats: bool = False,
):
    """Draft-FREE speculative decoding (prompt lookup): propose the k-1
    tokens that followed the most recent earlier occurrence of the
    current n-gram in the running context (prompt + generated), and
    verify with the same one-block-forward machinery as
    speculative_generate. No second model — this is the form the lm
    CLI's --sample-speculative-k reaches — and it shines on repetitive
    text (code, logs, structured data), where the continuation often
    already appeared verbatim. Same B=1 restriction and exactness
    contract as speculative_generate: bitwise greedy at temperature 0;
    at temperature > 0, rejection sampling against the one-hot proposal
    law (accept w.p. p(prop), resample the zeroed residual) — the
    output law is exactly plain sampling's (tests/test_spec_sampling).
    """
    b, s0 = prompt.shape
    if b != 1:
        raise ValueError(f"speculative decoding is the B=1 latency path "
                         f"(got batch {b}); use generate() for batches")
    if num_tokens < 1:
        raise ValueError("num_tokens must be >= 1")
    if k < 2:
        raise ValueError(f"k must be >= 2 (k={k} would propose nothing)")
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1 (got {ngram})")
    if s0 < ngram:
        raise ValueError(
            f"prompt length {s0} shorter than the lookup ngram {ngram}"
        )
    if s0 + num_tokens + k > model.max_seq:
        raise ValueError(
            f"prompt {s0} + {num_tokens} tokens + k={k} speculative slack "
            f"exceeds max_seq {model.max_seq}"
        )
    _validate_spec_sampling(temperature, key, top_k, top_p, model.vocab)
    run = _compiled_lookup_run(model, s0, num_tokens, int(k), int(ngram),
                               str(jnp.dtype(cache_dtype)),
                               float(max(temperature, 0.0)), int(top_k),
                               float(top_p))
    if key is None:
        key = jax.random.key(0)  # unused at temperature 0
    toks, n_out, rounds = run(params, prompt, key)
    if return_stats:
        return toks, _spec_stats(n_out, rounds, num_tokens)
    return toks


def speculative_generate(
    model: TransformerLM,
    params,
    draft_model: TransformerLM,
    draft_params,
    prompt: jnp.ndarray,          # (1, S0) int32 — latency path, B = 1
    num_tokens: int,
    *,
    k: int = 4,
    cache_dtype="float32",
    temperature: float = 0.0,
    key: jax.Array | None = None,
    top_k: int = 0,
    top_p: float = 0.0,
    return_stats: bool = False,
):
    """Speculative decoding: a cheap draft proposes k-token chains, the
    target verifies each chain with ONE cached block forward
    (decode_block) — between 1 and k target-quality tokens per target
    forward.

    At temperature 0 (default) acceptance is exact argmax matching and
    the output is the target's own greedy continuation — the draft
    changes the speed, not the tokens. At temperature > 0 (key
    required; top_k/top_p as in generate()) acceptance is REJECTION
    SAMPLING: the draft samples its own filtered law q, the target
    accepts each proposal w.p. min(1, p/q) and replaces a reject with a
    residual sample — the emitted law is exactly plain temperature
    sampling's, for any draft (the speculative sampling theorem;
    distribution-equality tests in tests/test_spec_sampling.py).

    Precision caveat, stated exactly: decode_block's batched
    contractions may tile/reassociate differently from the plain decode
    scan's, so the two paths agree to float rounding (~1e-4 observed),
    not bitwise; a greedy argmax whose top-2 logits tie within that
    drift could in principle differ. The equality test
    (tests/test_generate.py) and the bench's in-run assert have never
    observed a divergence. Both models must share the vocab; the draft
    is typically shallower/narrower. B must be 1 (per-row acceptance
    lengths diverge in a batch; speculation is the latency lever, plain
    generate() the throughput one).

    Returns tokens (1, num_tokens) int32 — or (tokens, stats) with
    `return_stats=True`, where stats carries the verify-round count and
    the mean accepted tokens per round (capped at the returned budget —
    the final round's overshoot never lands in the buffer).
    """
    b, s0 = prompt.shape
    if b != 1:
        raise ValueError(f"speculative decoding is the B=1 latency path "
                         f"(got batch {b}); use generate() for batches")
    if num_tokens < 1:
        raise ValueError("num_tokens must be >= 1")
    if k < 2:
        raise ValueError(f"k must be >= 2 (k={k} would draft nothing)")
    if model.vocab != draft_model.vocab:
        raise ValueError(
            f"target vocab {model.vocab} != draft vocab {draft_model.vocab}"
        )
    if s0 + num_tokens + k > min(model.max_seq, draft_model.max_seq):
        raise ValueError(
            f"prompt {s0} + {num_tokens} tokens + k={k} speculative slack "
            f"exceeds max_seq (target {model.max_seq}, draft "
            f"{draft_model.max_seq}; BOTH caches hold every position)"
        )
    _validate_spec_sampling(temperature, key, top_k, top_p, model.vocab)
    run = _compiled_spec_run(model, draft_model, s0, num_tokens, int(k),
                             str(jnp.dtype(cache_dtype)),
                             float(max(temperature, 0.0)), int(top_k),
                             float(top_p))
    if key is None:
        key = jax.random.key(0)  # unused at temperature 0
    toks, n_out, rounds = run(params, draft_params, prompt, key)
    if return_stats:
        return toks, _spec_stats(n_out, rounds, num_tokens)
    return toks


def generate(
    model: TransformerLM,
    params,
    prompt: jnp.ndarray,          # (B, S0) int32
    num_tokens: int,
    *,
    temperature: float = 0.0,
    key: jax.Array | None = None,
    cache_dtype="float32",
    top_k: int = 0,
    top_p: float = 0.0,
):
    """Prefill the prompt (one batched forward), then sample `num_tokens`
    continuations with the KV-cached decode scan.

    Returns (B, num_tokens) int32. Greedy argmax at temperature 0,
    categorical sampling otherwise (key required), optionally restricted
    by `top_k` (k most likely) and/or `top_p` (nucleus: smallest set
    reaching mass p) — see filter_logits. Prompt length + num_tokens
    must fit max_seq. `cache_dtype` "bfloat16" halves the KV cache bytes
    decode reads per token (attention scores/softmax stay f32); f32 is
    the exactness default the parity tests pin.
    """
    b, s0 = prompt.shape
    if num_tokens < 1:
        raise ValueError("num_tokens must be >= 1")
    if s0 + num_tokens > model.max_seq:
        raise ValueError(
            f"prompt {s0} + {num_tokens} new tokens exceeds max_seq "
            f"{model.max_seq}"
        )
    if temperature > 0 and key is None:
        raise ValueError("sampling (temperature > 0) needs a PRNG key")
    if top_k < 0 or top_k > model.vocab:
        raise ValueError(f"top_k {top_k} not in [0, vocab {model.vocab}]")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p {top_p} not in [0, 1]")
    if (top_k or top_p) and temperature <= 0:
        raise ValueError(
            "top_k/top_p restrict SAMPLING — set temperature > 0 "
            "(greedy argmax already takes the single most likely token)"
        )
    if key is None:
        key = jax.random.key(0)  # unused at temperature 0
    run = _compiled_run(model, s0, num_tokens, float(temperature),
                        str(jnp.dtype(cache_dtype)), int(top_k),
                        float(top_p))
    return run(params, prompt, key)
