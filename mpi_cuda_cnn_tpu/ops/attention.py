"""Scaled dot-product attention ops.

The reference has NO attention and no sequence axis anywhere (its op
universe is conv + FC + softmax, SURVEY.md §2.3-2.5 / §5.7) — these ops
exist because long-context support is a first-class capability of this
framework, not a parity item. They are the single-device oracles that the
sequence-parallel forms in parallel/sp.py (ring attention over 'seq' via
ppermute; Ulysses all-to-all head parallelism) are tested against.

Conventions: q/k/v are (B, S, H, D) — batch, sequence, heads, head_dim —
the layout whose S axis shards over the 'seq' mesh axis. Softmax is
max-subtracted (the same stabilization as ops/activations.stable_softmax,
cnn.c:125-143's trick) and, for the blockwise form, an *online* softmax:
running max m, running denominator l, running numerator o, renormalized
as each key/value block arrives — the algebra that makes ring attention
exact, not approximate.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import annotate

NEG_INF = -1e30  # large-but-finite: keeps fully-masked rows NaN-free


def attention(q, k, v, *, causal: bool = False):
    """Full (quadratic) scaled dot-product attention — the oracle.

    q: (B, S, H, D); k/v: (B, S, Hkv, D) with H % Hkv == 0 — Hkv < H is
    grouped-query attention (each kv head serves H/Hkv query heads;
    Hkv == 1 is MQA). Returns (B, S, H, D), f32 accumulation.
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hkv}")
    g = h // hkv
    with annotate("ops.attention"):
        qg = q.reshape(b, sq, hkv, g, d)
        scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
        ) * scale
        if causal:
            qi = jnp.arange(sq)[:, None]
            ki = jnp.arange(k.shape[1])[None, :]
            logits = jnp.where(ki <= qi, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum(
            "bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return out.reshape(b, sq, h, d).astype(q.dtype)


def repeat_kv(kv, n_heads: int):
    """Expand (B, S, Hkv, D) k/v to the full H query heads by repeating
    each kv head over its group — THE one definition of the grouping
    convention (query head qh reads kv head qh // (H/Hkv); group-major,
    matching the oracle's reshape and the flash kernels' index maps)."""
    hkv = kv.shape[2]
    if n_heads == hkv:
        return kv
    if n_heads % hkv:
        raise ValueError(f"heads {n_heads} not a multiple of kv heads {hkv}")
    return jnp.repeat(kv, n_heads // hkv, axis=2)


def yarn_inv_freq(dim: int, *, base: float, factor: float,
                  original_len: int, beta_fast: float, beta_slow: float):
    """The dim/2 rotary frequencies under YaRN scaling (Peng et al.,
    arXiv:2309.00071, as DeepSeek-V3's config spells it): pair i turns
    at f_i = base ** (-2i/dim); pairs that complete more than
    `beta_fast` turns over the original length keep f_i, pairs that
    complete fewer than `beta_slow` are slowed by `factor`, and a
    linear ramp between the two pair indices blends them. Static
    numbers (numpy): they fold into the program as a constant."""
    half = dim // 2
    f = base ** (-np.arange(half, dtype=np.float64) / half)

    def pair_of(turns):     # the pair that completes `turns` turns
        return (dim * math.log(original_len / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (f / factor * ramp + f * (1 - ramp)).astype(np.float32)


def rope(x, positions, *, base: float = 10000.0, inv_freq=None):
    """Rotary position embedding (rotate-half form) for x: (B, S, H, D).

    positions: (S,) absolute token positions — explicit, so sequence
    shards under SP pass their true global positions (pos_offset +
    arange, exactly like the learned table) — or (B, S) PER-ROW
    positions, the continuous-batching decode form (each serving slot
    sits at its own depth, so one batched forward spans many absolute
    positions; serve/engine.py). Angles are computed in f32 regardless
    of x.dtype (bf16 loses position precision past ~256); output
    returns in x.dtype. D must be even. `inv_freq` (half,) replaces
    base ** (-i/half): the YaRN-scaled frequencies of a long-context
    model (yarn_inv_freq). Pair i is entries i and i + half ("halves",
    not interleaved) in either case.
    """
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"rope needs an even head dim, got {d}")
    half = d // 2
    if inv_freq is None:
        freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    else:       # scaled frequencies (yarn_inv_freq), (half,)
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    # (S, 1, half) broadcasts over batch AND heads; (B, S, 1, half)
    # broadcasts over heads only — one expand serves both rank forms.
    cos = jnp.expand_dims(jnp.cos(angles), -2)
    sin = jnp.expand_dims(jnp.sin(angles), -2)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


def _block_logits(q, k, scale):
    return jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale


def online_softmax_block(carry, q, k, v, mask=None):
    """Fold one key/value block into the online-softmax state.

    carry = (o, m, l):
      o: (B, Sq, H, D) f32 — running unnormalized numerator,
      m: (B, H, Sq)    f32 — running row max,
      l: (B, H, Sq)    f32 — running denominator.
    mask: optional (Sq, Sk) bool, True = attend.

    Returns the updated carry. Finalize with o / l (see finalize_online).
    This is the exact blockwise-softmax recurrence (numerically identical
    to full softmax for any block order that respects the mask).
    """
    o, m, l = carry
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = _block_logits(q, k, scale)  # (B, H, Sq, Sk) f32
    if mask is not None:
        logits = jnp.where(mask[None, None, :, :], logits, NEG_INF)

    m_blk = jnp.max(logits, axis=-1)          # (B, H, Sq)
    m_new = jnp.maximum(m, m_blk)
    alpha = jnp.exp(m - m_new)                # rescale of old state
    p = jnp.exp(logits - m_new[..., None])    # (B, H, Sq, Sk)
    if mask is not None:
        # A fully-masked row keeps m == m_new == NEG_INF, where
        # exp(logit - m_new) = exp(0) = 1 would silently count masked
        # keys; zero them so l stays 0 and finalize_online yields zeros.
        p = jnp.where(mask[None, None, :, :], p, 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None]  # (B, Sq, H, 1) rescale
    o_new = o_new + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o_new, m_new, l_new


def init_online(q):
    """Fresh online-softmax carry for queries q: (B, Sq, H, D)."""
    b, sq, h, d = q.shape
    o = jnp.zeros((b, sq, h, d), jnp.float32)
    m = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    return o, m, l


def finalize_online(carry, dtype):
    """o / l with fully-masked rows (l == 0) mapped to zeros."""
    o, m, l = carry
    l_t = l.transpose(0, 2, 1)[..., None]  # (B, Sq, H, 1)
    return jnp.where(l_t > 0, o / jnp.maximum(l_t, 1e-30), 0.0).astype(dtype)


def blockwise_attention(q, k, v, *, block_size: int, causal: bool = False):
    """Full attention computed block-by-block with the online softmax —
    the single-device form of the ring-attention math (memory O(S·block)
    for the logits instead of O(S²)). Exact parity with attention()."""
    b, s, h, d = q.shape
    if s % block_size:
        raise ValueError(f"seq len {s} not divisible by block {block_size}")
    nblk = s // block_size
    kb = k.reshape(b, nblk, block_size, h, d)
    vb = v.reshape(b, nblk, block_size, h, d)
    qi = jnp.arange(s)[:, None]

    def fold(carry, blk):
        kj, vj, j = blk
        ki = j * block_size + jnp.arange(block_size)[None, :]
        mask = (ki <= qi) if causal else jnp.ones((s, block_size), bool)
        return online_softmax_block(carry, q, kj, vj, mask), None

    carry, _ = jax.lax.scan(
        fold,
        init_online(q),
        (kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4),
         jnp.arange(nblk)),
    )
    return finalize_online(carry, q.dtype)
