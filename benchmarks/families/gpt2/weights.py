"""The GPT-2 family's sizes and seeded f32 weights, one block at a
time, in the layout `TransformerLM.init` gives (models/transformer.py)
— the benchmark's own generator: build.py converts each block with the
program's quantizer and drops the f32 form; reference.py draws the same
block again from the same key and keeps it f32. Nothing the program made
(int8 values, scales, casts) ever reaches the reference.

Matrices are normal / sqrt(fan_in) as in `init`; the embeddings
normal / sqrt(d). Layernorm gains and biases are drawn near 1 and 0
(not exactly there, as `init` has them) so that a forward that dropped
them would not pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(cfg: dict) -> dict:
    """The sizes the block needs, from a configuration file's
    published keys (GPT-2 / GPTBigCode names). What the harness reads
    of them: `vocab` (the ids traffic draws from and `short` checks)
    and `max_seq` (no sampled sequence is padded past it)."""
    d, h = int(cfg["n_embd"]), int(cfg["n_head"])
    if d % h:
        raise ValueError(f"n_embd {d} not divisible by n_head {h}")
    if int(cfg["n_inner"]) != 4 * d:
        raise ValueError("the repo's block has a 4x MLP; n_inner "
                         f"{cfg['n_inner']} != 4 * {d}")
    return {
        "d": d, "heads": h, "hd": d // h,
        "n_kv": 1 if cfg["multi_query"] else h,
        "depth": int(cfg["n_layer"]), "ffn": int(cfg["n_inner"]),
        "vocab": int(cfg["vocab_size"]), "max_seq": int(cfg["n_positions"]),
        "eps": float(cfg["layer_norm_epsilon"]),
    }


def root_key(seed: int):
    return jax.random.key(int(seed))


def _dense(key, din, dout):
    return jax.random.normal(key, (din, dout), jnp.float32) / math.sqrt(din)


def _ln(key, d):
    kg, kb = jax.random.split(key)
    return {"g": 1.0 + 0.1 * jax.random.normal(kg, (d,), jnp.float32),
            "b": 0.1 * jax.random.normal(kb, (d,), jnp.float32)}


def block_f32(dm: dict, key, i):
    """Block i (a traced or concrete index) as an f32 tree."""
    d, hd, n_kv, ffn = dm["d"], dm["hd"], dm["n_kv"], dm["ffn"]
    k = jax.random.split(jax.random.fold_in(key, i + 1), 7)
    blk = {"ln1": _ln(k[0], d), "ln2": _ln(k[1], d)}
    if n_kv == dm["heads"]:
        blk["wqkv"] = _dense(k[2], d, 3 * d)
    else:
        blk["wq"] = _dense(k[2], d, d)
        blk["wkv"] = _dense(k[3], d, 2 * n_kv * hd)
    blk["wo"] = _dense(k[4], d, d)
    blk["w1"] = _dense(k[5], d, ffn)
    blk["w2"] = _dense(k[6], ffn, d)
    return blk


def top_f32(dm: dict, key):
    """Everything outside the blocks: embeddings, final norm, head."""
    d, v = dm["d"], dm["vocab"]
    k = jax.random.split(jax.random.fold_in(key, 0), 4)
    scale = 1.0 / math.sqrt(d)
    return {
        "tok_emb": jax.random.normal(k[0], (v, d), jnp.float32) * scale,
        "pos_emb": jax.random.normal(k[1], (dm["max_seq"], d),
                                     jnp.float32) * scale,
        "ln_f": _ln(k[2], d),
        "head": _dense(k[3], d, v),
    }
