"""The plain reference of the GPT-2 family: this block's forward in
float32 `jax.numpy` under "highest" matmul precision — no cache, no
paging, no kernels, no batching, nothing imported from the program.
Pre-LN LayerNorm with bias, MHA or multi-query attention over learned
positions, tanh-GELU 4x MLP, no linear biases, untied head (the block
`TransformerLM` is; the departures from each published model are in its
configuration file).

It runs layer by layer so that it fits beside nothing else on a 16 GB
chip: one f32 block is drawn from the seed (weights.block_f32), applied
to every sampled sequence, and dropped.

`lower` names a precision below the configuration's own, for the
control that has to come out not correct (README, "correct"): the
matmul weights (per output channel) and the keys and values (per
position and head) go through the benchmark's shared
`rounding.round_to`, everything else stays f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.rounding import round_to

from . import weights

_MATS = ("wqkv", "wq", "wkv", "wo", "w1", "w2")


def _layernorm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(dm, lower, x, blk):
    """One pre-LN block over one sequence x (T, d)."""
    t = x.shape[0]
    h, hd, n_kv = dm["heads"], dm["hd"], dm["n_kv"]
    if lower:
        blk = {**blk, **{m: round_to(blk[m], lower, 0)
                         for m in _MATS if m in blk}}
    y = _layernorm(x, blk["ln1"], dm["eps"])
    if "wqkv" in blk:
        q, k, v = jnp.split(y @ blk["wqkv"], 3, axis=-1)
    else:
        q = y @ blk["wq"]
        k, v = jnp.split(y @ blk["wkv"], 2, axis=-1)
    q = q.reshape(t, h, hd)
    k = k.reshape(t, n_kv, hd)
    v = v.reshape(t, n_kv, hd)
    if lower:
        k, v = round_to(k, lower, -1), round_to(v, lower, -1)
    if n_kv != h:                       # each kv head serves h/n_kv queries
        k = jnp.repeat(k, h // n_kv, axis=1)
        v = jnp.repeat(v, h // n_kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + o.reshape(t, h * hd) @ blk["wo"]
    y = _layernorm(x, blk["ln2"], dm["eps"])
    return x + _gelu_tanh(y @ blk["w1"]) @ blk["w2"]


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, lower):
    """The reference's jitted pieces for one configuration and one
    precision: draw a block, draw the top, embed, one block, logits."""
    dm = dict(dm_items)

    def embed(top_p, toks):
        return top_p["tok_emb"][toks] + top_p["pos_emb"][: toks.shape[0]]

    def logits(top_p, x, rows):
        head = round_to(top_p["head"], lower, 0) if lower else top_p["head"]
        return _layernorm(x[rows], top_p["ln_f"], dm["eps"]) @ head

    return {
        "draw_block": jax.jit(functools.partial(weights.block_f32, dm)),
        "draw_top": jax.jit(functools.partial(weights.top_f32, dm)),
        "embed": jax.jit(embed),
        "block": jax.jit(functools.partial(_block, dm, lower)),
        "logits": jax.jit(logits),
    }


def forward_logits(dm: dict, seed: int, seqs, rows, lowers=(None,)):
    """Logits of the reference at chosen positions.

    seqs: list of 1-D int token arrays, all padded by the caller to one
    length (causal attention makes right-padding harmless to earlier
    rows); rows: per sequence, an int array of positions, again of one
    length. For each entry of `lowers` (None = float32 itself) returns
    a list, one (len(rows[i]), vocab) f32 device array per sequence.
    One pass over the layers serves every entry, so the control costs
    no second draw of the weights.
    """
    key = weights.root_key(seed)
    dm_items = tuple(sorted(dm.items()))
    with jax.default_matmul_precision("highest"):
        fns = {lo: _jitted(dm_items, lo) for lo in lowers}
        plain = fns[lowers[0]]          # the draws do not depend on `lower`
        top_p = plain["draw_top"](key)
        xs = {lo: [plain["embed"](top_p, jnp.asarray(s)) for s in seqs]
              for lo in lowers}
        for i in range(dm["depth"]):
            blk = plain["draw_block"](key, i)
            for lo in lowers:
                xs[lo] = [fns[lo]["block"](x, blk) for x in xs[lo]]
        return [
            [fns[lo]["logits"](top_p, x, jnp.asarray(r))
             for x, r in zip(xs[lo], rows)]
            for lo in lowers
        ]
