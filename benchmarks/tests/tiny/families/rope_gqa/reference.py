"""The plain reference of this family: float32 `jax.numpy` at "highest"
matmul precision, nothing imported from the program. Pre-LN LayerNorm
with bias; queries from `wq`, keys and values from one `wkv`; rotary
positions in the rotate-half form (pair i of a head is entries i and
i + head/2, angle position * base ** (-i / (head/2))) on queries and
keys; each key/value head read by heads/kv_heads query heads; tanh-GELU
4x MLP; no linear biases; untied head.

Layer by layer: one f32 block is drawn from the seed, applied to every
sampled sequence and dropped. `lower` rounds, through the benchmark's
shared `rounding.round_to`, this family's weight matrices (per output
channel) and what it caches — the keys AFTER rotation, and the values
(per position and head).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.rounding import round_to

from . import weights

_MATS = ("wq", "wkv", "wo", "w1", "w2")


def _layernorm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _rotate(x, base):
    """x (T, heads, head) at positions 0..T-1, rotated pair by pair."""
    t, _, head = x.shape
    half = head // 2
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * base ** (-jnp.arange(half, dtype=jnp.float32) / half))
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _block(dm, lower, x, blk):
    """One pre-LN block over one sequence x (T, width)."""
    t = x.shape[0]
    nq, nkv, head = dm["q_heads"], dm["kv_heads"], dm["head"]
    if lower:
        blk = {**blk, **{m: round_to(blk[m], lower, 0) for m in _MATS}}
    y = _layernorm(x, blk["ln1"], dm["eps"])
    q = _rotate((y @ blk["wq"]).reshape(t, nq, head), dm["rope_base"])
    k, v = jnp.split(y @ blk["wkv"], 2, axis=-1)
    k = _rotate(k.reshape(t, nkv, head), dm["rope_base"])
    v = v.reshape(t, nkv, head)
    if lower:
        k, v = round_to(k, lower, -1), round_to(v, lower, -1)
    # Query head h reads key/value head h // (nq / nkv).
    q = q.reshape(t, nkv, nq // nkv, head)
    s = jnp.einsum("qgrd,kgd->grqk", q, k) / np.sqrt(head)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v)
    x = x + o.reshape(t, nq * head) @ blk["wo"]
    y = _layernorm(x, blk["ln2"], dm["eps"])
    return x + _gelu_tanh(y @ blk["w1"]) @ blk["w2"]


@functools.lru_cache(maxsize=None)
def _jitted(dm_items, lower):
    dm = dict(dm_items)

    def logits(top_p, x, rows):
        head = round_to(top_p["head"], lower, 0) if lower else top_p["head"]
        return _layernorm(x[rows], top_p["ln_f"], dm["eps"]) @ head

    return {"block": jax.jit(functools.partial(_block, dm, lower)),
            "logits": jax.jit(logits)}


def forward_logits(dm: dict, seed: int, seqs, rows, lowers=(None,)):
    """Logits of the reference at chosen positions: `seqs` 1-D int token
    arrays of one length (right-padding is harmless under causal
    attention), `rows` per sequence an int array of positions, of one
    length. For each entry of `lowers` (None = float32 itself) a list,
    one (len(rows[i]), vocab) f32 array per sequence. One pass over the
    layers and one draw of each block serve every entry."""
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
            for lo in lowers:
                xs[lo] = [fns[lo]["block"](x, blk) for x in xs[lo]]
        return [
            [fns[lo]["logits"](top_p, x, jnp.asarray(r))
             for x, r in zip(xs[lo], rows)]
            for lo in lowers
        ]
