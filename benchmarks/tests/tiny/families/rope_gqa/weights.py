"""A second, test-sized family: rotary positions and grouped-query
attention (1 < key/value heads < heads), the block the program serves
as `TransformerLM(pos="rope", kv_heads=...)` and the GPT-2 family's
key names cannot describe. It is here to show that a configuration of
another architecture is files and no edit: its `dims` reads other
published key names (the Llama / GPT-NeoX spelling) and gives other
size names than the first family's, so shared code that still reads a
block's key fails on it.

Seeded f32 draws, block by block, for build.py and reference.py alone.
Matrices are normal / sqrt(fan_in), the embedding normal / sqrt(width);
LayerNorm gains and biases are drawn near 1 and 0, so that a forward
that dropped them would not pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ROPE_BASE = 10000.0     # ops/attention.rope's, which takes no other


def dims(cfg: dict) -> dict:
    """The sizes, from `hidden_size`, `num_attention_heads`,
    `num_key_value_heads`, `num_hidden_layers`, `intermediate_size`,
    `max_position_embeddings`, `vocab_size`, `layer_norm_eps`,
    `rope_theta`. What the program's block cannot be is refused."""
    width, q_heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv_heads = int(cfg["num_key_value_heads"])
    if width % q_heads or (width // q_heads) % 2:
        raise ValueError(f"hidden_size {width} over {q_heads} heads: want "
                         "a whole, even head size (rotate-half rope)")
    if not 1 < kv_heads < q_heads or q_heads % kv_heads:
        raise ValueError(f"num_key_value_heads {kv_heads}: this family is "
                         f"grouped-query, a divisor of {q_heads} between "
                         "1 and it (MHA and MQA are the GPT-2 family's)")
    if int(cfg["intermediate_size"]) != 4 * width:
        raise ValueError("the program's block has a 4x MLP; "
                         f"intermediate_size {cfg['intermediate_size']}")
    if float(cfg["rope_theta"]) != ROPE_BASE:
        raise ValueError(f"the program's rope has base {ROPE_BASE}; "
                         f"rope_theta {cfg['rope_theta']}")
    return {
        "width": width, "q_heads": q_heads, "kv_heads": kv_heads,
        "head": width // q_heads, "layers": int(cfg["num_hidden_layers"]),
        "mlp": int(cfg["intermediate_size"]),
        "vocab": int(cfg["vocab_size"]),
        "max_seq": int(cfg["max_position_embeddings"]),
        "eps": float(cfg["layer_norm_eps"]), "rope_base": ROPE_BASE,
    }


def root_key(seed: int):
    return jax.random.key(int(seed))


def _dense(key, din, dout):
    return jax.random.normal(key, (din, dout), jnp.float32) / math.sqrt(din)


def _norm(key, width):
    kg, kb = jax.random.split(key)
    return {"g": 1.0 + 0.1 * jax.random.normal(kg, (width,), jnp.float32),
            "b": 0.1 * jax.random.normal(kb, (width,), jnp.float32)}


def block_f32(dm: dict, key, i):
    """Block i (a traced or concrete index) as an f32 tree, in the
    layout `TransformerLM.init` gives a grouped-query block."""
    width, kv_out = dm["width"], 2 * dm["kv_heads"] * dm["head"]
    k = jax.random.split(jax.random.fold_in(key, i + 1), 7)
    return {
        "ln1": _norm(k[0], width), "ln2": _norm(k[1], width),
        "wq": _dense(k[2], width, width), "wkv": _dense(k[3], width, kv_out),
        "wo": _dense(k[4], width, width),
        "w1": _dense(k[5], width, dm["mlp"]),
        "w2": _dense(k[6], dm["mlp"], width),
    }


def top_f32(dm: dict, key):
    """Everything outside the blocks: the token embedding, the final
    norm, the head. There is no position table."""
    width, vocab = dm["width"], dm["vocab"]
    k = jax.random.split(jax.random.fold_in(key, 0), 3)
    return {
        "tok_emb": jax.random.normal(k[0], (vocab, width), jnp.float32)
        / math.sqrt(width),
        "ln_f": _norm(k[1], width),
        "head": _dense(k[2], width, vocab),
    }
