"""The plain reference of the `window_moe` family: float32 `jax.numpy` at
"highest" matmul precision, nothing imported from the program, no
cache, no kernels, no sorting of tokens, no grouped product.

Layer l over one sequence x (T, width), RMS(x; g) = x * rsqrt(mean x^2
+ eps) * g:

- the ROUTER reads the layer's INPUT: z = x W_r; p = softmax(z) over
  all experts; the top_k largest p are chosen; w_e = p_e / sum of the
  chosen p (0 for the others).
- a = RMS(x; g1); q = a W_q (heads x head_dim), k = a W_k, v = a W_v
  (kv_heads x head_dim), no bias. Where `rope_layout[l]` is 1, q and k
  are rotated over all head_dim dims at `rope_theta` (pair i is entries
  i and i + head_dim/2, "halves", as in the program: with seeded
  weights the interleaved layout differs by a permutation of columns);
  where 0 they are not, and the layer knows positions by causality
  alone.
- causal attention, scale 1/sqrt(head_dim), heads/kv_heads query heads
  a K/V head; where `window_layout[l]` is 1 query i sees key j iff
  i - window < j <= i (the window counts the query itself), where 0
  every j <= i. x' = x + concat(heads) W_o. Queries are taken in
  blocks of rows so that the scores of a 16,384-token sequence fit.
- b = RMS(x'; g2); out = x' + sum over e of w_e (relu(b W_g^e) *
  (b W_u^e)) W_d^e: every expert is applied to every row and weighted,
  0 where not chosen. The expert MLP reads b (after attention); only
  its routing was decided from x (before).

After the last layer RMS and the head, at the rows asked for.

`lower` rounds, through `rounding.round_to`, the weight matrices (per
output channel; the f32 router and the gains are not weights of the
lower precision) and what is cached: k (after rotation) and v, per
position and head.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.rounding import round_to

from . import weights

_MATS = ("wq", "wk", "wv", "wo")
ROW_BLOCK = 128     # queries a block of attention scores


def _rms(x, p, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["g"]


def _rotate(x, theta):
    """x (T, heads, head_dim) at positions 0..T-1."""
    t, half = x.shape[0], x.shape[-1] // 2
    angle = (np.arange(t)[:, None]
             * theta ** (-np.arange(half) / half)[None, :])[:, None, :]
    cos, sin = (jnp.asarray(f(angle), jnp.float32) for f in (np.cos, np.sin))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def route(dm, x, router):
    """(T, experts) weights: w_e for the chosen experts, 0 elsewhere."""
    p = jax.nn.softmax(x @ router["gate"], axis=-1)
    cut = jnp.sort(p, axis=-1)[:, -dm["top_k"]][:, None]
    w = jnp.where(p >= cut, p, 0.0)
    return w / w.sum(-1, keepdims=True)


def reglu(y, wg, wu, wd):
    return (jax.nn.relu(y @ wg) * (y @ wu)) @ wd


def attention(dm, rotary, windowed, lower, a, blk):
    t, h, kv, hd = a.shape[0], dm["heads"], dm["kv_heads"], dm["head_dim"]
    q = (a @ blk["wq"]).reshape(t, h, hd)
    k = (a @ blk["wk"]).reshape(t, kv, hd)
    v = (a @ blk["wv"]).reshape(t, kv, hd)
    if rotary:
        q, k = _rotate(q, dm["rope_theta"]), _rotate(k, dm["rope_theta"])
    if lower:
        k, v = round_to(k, lower, -1), round_to(v, lower, -1)
    blocks = -(-t // ROW_BLOCK)
    qg = jnp.pad(q, ((0, blocks * ROW_BLOCK - t), (0, 0), (0, 0))).reshape(
        blocks, ROW_BLOCK, kv, h // kv, hd)
    keys = jnp.arange(t)[None, :]

    def rows(args):
        r0, qb = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(hd)
        at = (r0 + jnp.arange(ROW_BLOCK))[:, None]
        see = keys <= at
        if windowed:
            see = see & (keys > at - dm["window"])
        p = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    out = jax.lax.map(rows, (jnp.arange(blocks) * ROW_BLOCK, qg))
    return out.reshape(blocks * ROW_BLOCK, h * hd)[:t] @ blk["wo"]


def _block(dm, rotary, windowed, lower, x, blk):
    """One layer over one sequence x (T, width)."""
    if lower:
        blk = {**blk, **{m: round_to(blk[m], lower, 0) for m in _MATS},
               "experts": {m: round_to(w, lower, 1)
                           for m, w in blk["experts"].items()}}
    w = route(dm, x, blk["router"])
    x = x + attention(dm, rotary, windowed, lower,
                      _rms(x, blk["ln1"], dm["eps"]), blk)
    b = _rms(x, blk["ln2"], dm["eps"])
    bank = blk["experts"]

    def add(acc, e):
        wg, wu, wd, we = e
        return acc + we[:, None] * reglu(b, wg, wu, wd), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(x),
                        (bank["wg"], bank["wu"], bank["wd"], w.T))
    return x + y


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, lower):
    dm = dict(dm_items)

    def logits(top_p, x, rows):
        head = round_to(top_p["head"], lower, 0) if lower else top_p["head"]
        return _rms(x[rows], top_p["ln_f"], dm["eps"]) @ head

    kinds = set(zip(dm["rope_layout"], dm["window_layout"]))
    return {"block": {kind: jax.jit(functools.partial(
                _block, dm, bool(kind[0]), bool(kind[1]), lower))
                for kind in kinds},
            "logits": jax.jit(logits)}


def forward_logits(dm: dict, seed: int, seqs, rows, lowers=(None,)):
    """Logits of the reference at chosen positions: `seqs` 1-D int token
    arrays of one length (right-padding is harmless under causal
    attention, and a token's experts depend on that token alone), `rows`
    per sequence an int array of positions, of one length. For each
    entry of `lowers` (None = float32 itself) a list, one (len(rows[i]),
    vocab) f32 array per sequence. One pass over the layers and one
    draw of each f32 layer serve every entry."""
    key = weights.root_key(seed)
    dm_items = tuple(sorted(dm.items()))
    with jax.default_matmul_precision("highest"):
        fns = {lo: _jitted(dm_items, lo) for lo in lowers}
        top_p = jax.jit(functools.partial(weights.top_f32, dm))(key)
        draw = jax.jit(functools.partial(weights.block_f32, dm))
        xs = {lo: [top_p["tok_emb"][jnp.asarray(s)] for s in seqs]
              for lo in lowers}
        for i in range(dm["layers"]):
            blk = draw(key, i)
            kind = (dm["rope_layout"][i], dm["window_layout"][i])
            for lo in lowers:
                xs[lo] = [fns[lo]["block"][kind](x, blk) for x in xs[lo]]
            del blk
        return [
            [fns[lo]["logits"](top_p, x, jnp.asarray(r))
             for x, r in zip(xs[lo], rows)]
            for lo in lowers
        ]
