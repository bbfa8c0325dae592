"""The plain reference of the `mla_moe` family: float32 `jax.numpy` at
"highest" matmul precision, nothing imported from the program, no
cache, no kernels, no sorting of tokens.

Per layer, over one sequence x (T, width): h = x + Attn(RMS x);
x' = h + FFN(RMS h). RMS(x) = x * rsqrt(mean x^2 + eps) * g.

Attention (MLA, the MATERIALIZED read only): c_q = RMS(y W_dq);
[q_n ; q_r] = c_q W_uq a head (nope + rope); [c ; k_r] = y W_dkv;
c <- RMS(c); q_r, k_r <- rotary (k_r one for all heads); [k_n ; v] =
c W_ukv a head; score(t, s) = sigma (q_n.k_n + q_r.k_r), causal, f32
softmax; out = [o_1 .. o_H] W_o. sigma = m^2 / sqrt(nope + rope), m =
0.1 mscale_all_dim ln(factor) + 1. Rotary: YaRN frequencies (pair i of
the rope dims turns at f_i = theta^(-2i/rope); low/high = the pair
indices that make beta_fast / beta_slow turns over the original
length; ramp_i = clamp((i - low)/(high - low), 0, 1); inv_i = f_i /
factor * ramp_i + f_i (1 - ramp_i)); pair i is entries i and i +
rope/2 ("halves"), as in the program — with seeded weights the
published interleaved layout differs by a permutation of W's columns.
Queries are taken in blocks of rows so that the scores fit.

FFN: SwiGLU down(silu(gate y) * up y) in a dense layer. In an expert
layer, this chip's share: the router scores all `routed` experts in
f32, s = sigmoid(y W_g); s' = s + bias chooses only; `groups` groups, a
group's score the sum of its two largest s', the `top_groups` best
stay, among their experts the `top_k` largest s' are chosen; w_e = s_e
of the chosen, normalised, times `route_scale`. out = sum over chosen
AND held e of w_e E_e(y) + Shared(y): each held expert is applied to
every row and weighted (0 where not chosen). What absent experts
would add is left out, as in the program.

`lower` rounds, through `rounding.round_to`, the weight matrices (per
output channel; the f32 router and the gains are not weights of the
lower precision) and what is cached: the latent row [c ; k_r] after
normalisation and rotation, per position.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.rounding import round_to

from . import weights

_MATS = ("wdq", "wuq", "wdkv", "wukv", "wo")
_GATED = ("wg", "wu", "wd")
ROW_BLOCK = 256     # queries a block of attention scores


def _rms(x, p, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["g"]


def yarn_frequencies(dm):
    factor, original, fast, slow, _, _ = dm["yarn"]
    dim, theta = dm["rope"], dm["rope_theta"]
    f = theta ** (-2.0 * np.arange(dim // 2) / dim)

    def pair_of(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(fast)), 0)
    high = min(math.ceil(pair_of(slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return f / factor * ramp + f * (1.0 - ramp)


def softmax_scale(dm):
    factor, _, _, _, mscale, mscale_all = dm["yarn"]
    if mscale != mscale_all:
        raise ValueError("mscale != mscale_all_dim scales cos and sin")
    m = 0.1 * mscale_all * math.log(factor) + 1.0 if factor > 1 else 1.0
    return m * m / math.sqrt(dm["nope"] + dm["rope"])


def _rotate(x, dm):
    """x (T, ..., rope) at positions 0..T-1."""
    t, half = x.shape[0], dm["rope"] // 2
    angle = (np.arange(t)[:, None] * yarn_frequencies(dm)[None, :])
    angle = angle.reshape((t,) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = (jnp.asarray(f(angle), jnp.float32) for f in (np.cos, np.sin))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _swiglu(y, p):
    return (jax.nn.silu(y @ p["wg"]) * (y @ p["wu"])) @ p["wd"]


def route(dm, y, router):
    """(T, routed) weights: w_e for the chosen experts, 0 elsewhere."""
    t = y.shape[0]
    s = jax.nn.sigmoid(y @ router["gate"])
    chooser = (s + router["bias"]).reshape(t, dm["groups"], -1)
    group_score = jnp.sort(chooser, axis=-1)[..., -2:].sum(-1)
    cut = jnp.sort(group_score, axis=-1)[:, -dm["top_groups"]][:, None]
    chooser = jnp.where((group_score >= cut)[..., None], chooser, -jnp.inf)
    chooser = chooser.reshape(t, -1)
    cut = jnp.sort(chooser, axis=-1)[:, -dm["top_k"]][:, None]
    w = jnp.where(chooser >= cut, s, 0.0)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * dm["route_scale"]


def _attention(dm, lower, y, blk):
    t, h = y.shape[0], dm["heads"]
    nope, rope, v, rank = dm["nope"], dm["rope"], dm["v"], dm["kv_rank"]
    cq = _rms(y @ blk["wdq"], blk["q_norm"], dm["eps"])
    q = (cq @ blk["wuq"]).reshape(t, h, nope + rope)
    qn, qr = q[..., :nope], _rotate(q[..., nope:], dm)
    ckr = y @ blk["wdkv"]
    row = jnp.concatenate([_rms(ckr[:, :rank], blk["kv_norm"], dm["eps"]),
                           _rotate(ckr[:, rank:], dm)], axis=-1)
    if lower:
        row = round_to(row, lower, -1)
    c, kr = row[:, :rank], row[:, rank:]
    kv = (c @ blk["wukv"]).reshape(t, h, nope + v)
    kn, val = kv[..., :nope], kv[..., nope:]
    scale, out = softmax_scale(dm), []
    for r0 in range(0, t, ROW_BLOCK):
        rows = slice(r0, min(r0 + ROW_BLOCK, t))
        s = (jnp.einsum("qhd,khd->hqk", qn[rows], kn)
             + jnp.einsum("qhd,kd->hqk", qr[rows], kr)) * scale
        causal = (jnp.arange(t)[rows][:, None] >= jnp.arange(t)[None, :])
        p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, val))
    return jnp.concatenate(out, axis=0).reshape(t, h * v) @ blk["wo"]


def _block(dm, lower, x, blk):
    """One block over one sequence x (T, width); the layer's kind is
    what `blk` holds."""
    if lower:
        routed = "router" in blk
        low = {m: round_to(blk[m], lower, 0)
               for m in _MATS + (() if routed else _GATED)}
        if routed:
            low["shared"] = {m: round_to(w, lower, 0)
                             for m, w in blk["shared"].items()}
            low["experts"] = {m: round_to(w, lower, 1)
                              for m, w in blk["experts"].items()}
        blk = {**blk, **low}
    x = x + _attention(dm, lower, _rms(x, blk["ln1"], dm["eps"]), blk)
    y = _rms(x, blk["ln2"], dm["eps"])
    if "router" not in blk:
        return x + _swiglu(y, blk)
    w = route(dm, y, blk["router"])
    out = _swiglu(y, blk["shared"])
    for j, e in enumerate(weights.held_ids(dm)):
        out = out + w[:, e:e + 1] * _swiglu(
            y, {m: blk["experts"][m][j] for m in _GATED})
    return x + out


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, lower):
    dm = dict(dm_items)

    def logits(top_p, x, rows):
        head = round_to(top_p["head"], lower, 0) if lower else top_p["head"]
        return _rms(x[rows], top_p["ln_f"], dm["eps"]) @ head

    return {"block": jax.jit(functools.partial(_block, dm, lower)),
            "logits": jax.jit(logits)}


def forward_logits(dm: dict, seed: int, seqs, rows, lowers=(None,)):
    """Logits of the reference at chosen positions: `seqs` 1-D int token
    arrays of one length (right-padding is harmless under causal
    attention, and a token's experts depend on that token alone), `rows`
    per sequence an int array of positions, of one length. For each
    entry of `lowers` (None = float32 itself) a list, one (len(rows[i]),
    vocab) f32 array per sequence. One pass over the layers and one
    draw of each f32 block serve every entry."""
    key = weights.root_key(seed)
    dm_items = tuple(sorted(dm.items()))
    with jax.default_matmul_precision("highest"):
        fns = {lo: _jitted(dm_items, lo) for lo in lowers}
        top_p = jax.jit(functools.partial(weights.top_f32, dm))(key)
        draw = jax.jit(functools.partial(weights.block_f32, dm),
                       static_argnums=2)
        xs = {lo: [top_p["tok_emb"][jnp.asarray(s)] for s in seqs]
              for lo in lowers}
        for i in range(dm["layers"]):
            blk = draw(key, i, i < dm["dense_layers"])
            for lo in lowers:
                xs[lo] = [fns[lo]["block"](x, blk) for x in xs[lo]]
            del blk
        return [
            [fns[lo]["logits"](top_p, x, jnp.asarray(r))
             for x, r in zip(xs[lo], rows)]
            for lo in lowers
        ]
