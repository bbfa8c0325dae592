"""The plain reference of the `sparse_linear` family: float32
`jax.numpy` at "highest" matmul precision, nothing imported from the
program, no cache, no pages, no kernels.

Over one sequence x (T, width), RMS(x; g) = x * rsqrt(mean x^2 + eps) *
g, r = scale_depth / sqrt(published layers):
x0 = scale_emb * E[tok]; x <- x + r * Mixer(RMS(x; g1));
x <- x + r * (silu(b W_g) * (b W_u)) W_d, b = RMS(x; g2);
logits = (RMS(x; g_f) * dim_model_base / hidden_size) W_head.

SPARSE layer ("attn"), a = RMS(x; g1): q = a W_q (heads x head_dim),
k = a W_k, v = a W_v (kv_heads x head_dim); q and k RMS-normed over
the head's dims with a gain; no positional encoding. Compressed key
j = mean(k[stride j .. stride j + kernel - 1]). For the query at t and
K/V head g: p_h = softmax over the j with stride j + kernel - 1 <= t of
q_{t,h} . kc_j / sqrt(head_dim), for each of g's heads; P_j their sum;
block b (keys block b .. block b + block - 1) scores the largest P_j
among the compressed keys whose rows overlap it; chosen = blocks <
init_blocks, the blocks that overlap keys t - window + 1 .. t, and the
topk best of the other blocks up to t's own (stable order: ties to the
lower block); while t + 1 < dense_len every block up to t's own.
o_{t,h} = softmax over the keys s <= t in chosen blocks of q_{t,h} .
k_s / sqrt(head_dim), times v. out = (o * sigmoid(a W_gate)) W_o.
Queries are taken in blocks of rows so that a 65,536-token sequence
fits.

LINEAR layer: q, k, v = a W_q, a W_k, a W_v, heads x head_dim each; the
same q/k RMS; rotary on q and k over all head_dim dims at rope_theta
(pair i is entries i and i + head_dim/2, "halves"); l_h = exp(-2^(-slope
(h + 1) / heads)). S_t = l_h S_{t-1} + k_t^T v_t, o_t = q_t S_t /
sqrt(head_dim), computed a block of R rows at a time from the state
the rows before left: O = ((Q K^T) * D) V + L (Q S), D_ij = l^(i-j)
for i >= j else 0, L_i = l^(i+1); S' = l^R S + sum_j l^(R-1-j) k_j^T
v_j. out = (RMS_head(o; g_o) * sigmoid(a W_gate)) W_o, RMS over each
head's dims.

The last layer is computed at the rows asked for alone (its keys, or
its state, from every row); then RMS and the head.

`lower` rounds, through `rounding.round_to`, the weight matrices (per
output channel; gains are not weights of the lower precision) and what
is cached: k and v per position and head, the compressed keys (means of
the rounded k, rounded again as the cache stores them). A linear
layer's state is float32 in the configuration and stays so.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.rounding import round_to

from . import weights

_MATS = ("wq", "wk", "wv", "wo", "wgate", "wg", "wu", "wd")
ROW_BLOCK = 32      # queries a block of a sparse layer's scores
STATE_BLOCK = 256   # rows a block of a linear layer's recurrence
MLP_BLOCK = 2048    # rows a block of the MLP's hidden activations


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


def _in_blocks(fn, rows, block, *xs):
    """fn over `rows` rows of each of xs, `block` at a time (the tail
    padded with zeros and dropped): (rows, ...) out."""
    n = -(-rows // block)
    pad = lambda x: jnp.pad(  # noqa: E731
        x, ((0, n * block - rows),) + ((0, 0),) * (x.ndim - 1)).reshape(
        n, block, *x.shape[1:])
    out = jax.lax.map(fn, tuple(pad(x) for x in xs))
    return out.reshape(n * block, *out.shape[2:])[:rows]


def compressed_keys(dm, k):
    """k (T, kv, hd) -> (J, kv, hd): the mean of every `kernel` keys a
    `stride`, the complete ones only."""
    kernel, stride = dm["select"][:2]
    j = max(k.shape[0] - kernel, -stride) // stride + 1
    if j <= 0:
        return jnp.zeros((0,) + k.shape[1:], k.dtype)
    rows = stride * np.arange(j)[:, None] + np.arange(kernel)[None, :]
    return jnp.mean(k[rows], axis=1)


def chosen_blocks(dm, q, kc, at, nblocks):
    """The blocks the queries q (R, kv, g, hd) at positions `at` (R,)
    read, a K/V head: (kv, R, nblocks) bool."""
    kernel, stride, block, topk, init, window, dense = dm["select"]
    hd, jn = q.shape[-1], kc.shape[0]
    blk = jnp.arange(nblocks)[None, :]
    t = at[:, None]
    upto = blk <= t // block
    if jn == 0:
        return jnp.broadcast_to(upto[None], (q.shape[1],) + upto.shape)
    s = jnp.einsum("qhgd,jhd->hgqj", q, kc) / math.sqrt(hd)
    done = (stride * jnp.arange(jn) + kernel - 1)[None, :] <= t      # (R, J)
    p = jnp.where(done, jax.nn.softmax(jnp.where(done, s, -jnp.inf), axis=-1),
                  0.0).sum(axis=1)                                   # (kv, R, J)
    # The compressed keys whose rows overlap block b: stride j + kernel
    # - 1 >= block b and stride j <= block b + block - 1.
    first = -(-(block * np.arange(nblocks) - kernel + 1) // stride)
    last = (block * np.arange(nblocks) + block - 1) // stride
    span = int((last - first).max()) + 1
    js = first[:, None] + np.arange(span)[None, :]                   # (B, span)
    ok = (js >= 0) & (js <= last[:, None]) & (js < jn)
    score = jnp.max(jnp.where(ok, p[..., np.clip(js, 0, jn - 1)], 0.0),
                    axis=-1)                                         # (kv, R, B)
    near = (blk < init) | (blk >= jnp.maximum(t - window + 1, 0) // block)
    far = upto & ~near
    order = jnp.argsort(jnp.where(far, -score, jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)          # a block's place in the order
    picked = far & (rank < topk)
    return upto & jnp.where(t + 1 < dense, True, near | picked)


def sparse_attention(dm, lower, a, blk, rows):
    t, h, kv, hd = a.shape[0], dm["heads"], dm["kv_heads"], dm["head_dim"]
    block = dm["select"][2]
    q = _rms((a[rows] @ blk["wq"]).reshape(-1, h, hd), blk["q_norm"],
             dm["eps"])
    k = _rms((a @ blk["wk"]).reshape(t, kv, hd), blk["k_norm"], dm["eps"])
    v = (a @ blk["wv"]).reshape(t, kv, hd)
    if lower:
        k, v = round_to(k, lower, -1), round_to(v, lower, -1)
    kc = compressed_keys(dm, k)
    if lower and kc.shape[0]:
        kc = round_to(kc, lower, -1)
    nblocks = -(-t // block)
    keys = jnp.arange(t)

    def some(args):
        at, qb = args
        picks = chosen_blocks(dm, qb, kc, at, nblocks)           # (kv, R, B)
        see = picks[..., keys // block] & (keys[None, :] <= at[:, None])
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(see[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    o = _in_blocks(some, q.shape[0], ROW_BLOCK, jnp.asarray(rows),
                   q.reshape(-1, kv, h // kv, hd))
    gate = jax.nn.sigmoid(a[rows] @ blk["wgate"])
    return (o.reshape(-1, h * hd) * gate) @ blk["wo"]


def linear_attention(dm, a, blk, rows):
    t, h, hd = a.shape[0], dm["heads"], dm["head_dim"]
    q, k = (_rotate(_rms((a @ blk[m]).reshape(t, h, hd), blk[n], dm["eps"]),
                    dm["rope_theta"])
            for m, n in (("wq", "q_norm"), ("wk", "k_norm")))
    v = (a @ blk["wv"]).reshape(t, h, hd)
    rate = 2.0 ** (-dm["slope"] * np.arange(1, h + 1) / h)      # -log l_h
    r = STATE_BLOCK
    i = np.arange(r)
    gap = i[:, None] - i[None, :]
    decay = jnp.asarray(np.where(gap >= 0, np.exp(
        -rate[:, None, None] * np.maximum(gap, 0)), 0.0), jnp.float32)
    carry = jnp.asarray(np.exp(-rate[:, None] * (i + 1)), jnp.float32)
    into = jnp.asarray(np.exp(-rate[:, None] * (r - 1 - i)), jnp.float32)
    whole = jnp.asarray(np.exp(-rate * r), jnp.float32)

    def step(state, qkv):
        qb, kb, vb = qkv                                        # (R, h, hd)
        o = jnp.einsum("hqk,khd->qhd",
                       jnp.einsum("qhd,khd->hqk", qb, kb) * decay, vb)
        o = o + jnp.einsum("qhd,hde->qhe", qb, state) * carry.T[:, :, None]
        state = whole[:, None, None] * state + jnp.einsum(
            "khd,khe->hde", kb * into.T[:, :, None], vb)
        return state, o

    n = -(-t // r)
    pad = lambda x: jnp.pad(  # noqa: E731
        x, ((0, n * r - t), (0, 0), (0, 0))).reshape(n, r, h, hd)
    _, o = jax.lax.scan(step, jnp.zeros((h, hd, hd), jnp.float32),
                        (pad(q), pad(k), pad(v)))
    o = o.reshape(n * r, h, hd)[:t][rows] / math.sqrt(hd)
    o = _rms(o, blk["o_norm"], dm["eps"]).reshape(-1, h * hd)
    return (o * jax.nn.sigmoid(a[rows] @ blk["wgate"])) @ blk["wo"]


def _block(dm, kind, lower, x, blk, rows):
    """One layer over one sequence x (T, width), at `rows` (all of
    them, in order, for every layer but the last)."""
    if lower:
        blk = {**blk, **{m: round_to(blk[m], lower, 0) for m in _MATS}}
    a = _rms(x, blk["ln1"], dm["eps"])
    mixed = (sparse_attention(dm, lower, a, blk, rows) if kind == "attn"
             else linear_attention(dm, a, blk, rows))
    x = x[rows] + dm["residual_scale"] * mixed
    b = _rms(x, blk["ln2"], dm["eps"])
    mlp = _in_blocks(
        lambda bb: (jax.nn.silu(bb[0] @ blk["wg"]) * (bb[0] @ blk["wu"]))
        @ blk["wd"], b.shape[0], min(MLP_BLOCK, b.shape[0]), b)
    return x + dm["residual_scale"] * mlp


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, lower):
    dm = dict(dm_items)

    def logits(top_p, x):
        head = round_to(top_p["head"], lower, 0) if lower else top_p["head"]
        return (_rms(x, top_p["ln_f"], dm["eps"]) * dm["logit_scale"]) @ head

    return {"block": {kind: jax.jit(functools.partial(_block, dm, kind, lower))
                      for kind in set(dm["mixers"])},
            "logits": jax.jit(logits)}


def forward_logits(dm: dict, seed: int, seqs, rows, lowers=(None,)):
    """Logits of the reference at chosen positions: `seqs` 1-D int token
    arrays of one length (right-padding is harmless: attention, the
    selection and the recurrence are all causal), `rows` per sequence an
    int array of positions, of one length. For each entry of `lowers`
    (None = float32 itself) a list, one (len(rows[i]), vocab) f32 array
    per sequence. One sequence at a time goes through the layers, each
    f32 layer drawn again as it is needed (a draw is milliseconds; every
    sequence's activations at once would not fit at 65,536 rows)."""
    key = weights.root_key(seed)
    dm_items = tuple(sorted(dm.items()))
    last = dm["layers"] - 1
    with jax.default_matmul_precision("highest"):
        fns = {lo: _jitted(dm_items, lo) for lo in lowers}
        top_p = jax.jit(functools.partial(weights.top_f32, dm))(key)
        draw = jax.jit(functools.partial(weights.block_f32, dm),
                       static_argnums=1)
        out = {lo: [] for lo in lowers}
        for seq, want in zip(seqs, rows):
            x0 = dm["emb_scale"] * top_p["tok_emb"][jnp.asarray(seq)]
            every = jnp.arange(len(seq))
            xs = {lo: x0 for lo in lowers}
            for i in range(dm["layers"]):
                blk = draw(key, i)
                at = jnp.asarray(want) if i == last else every
                for lo in lowers:
                    xs[lo] = fns[lo]["block"][dm["mixers"][i]](
                        xs[lo], blk, at)
                del blk
            for lo in lowers:
                out[lo].append(fns[lo]["logits"](top_p, xs[lo]))
        return [out[lo] for lo in lowers]
