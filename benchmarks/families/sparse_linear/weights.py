"""The `sparse_linear` family: a MiniCPM-SALA-shaped decoder — by a
per-layer list (`mixer_types`) either a SPARSE softmax layer
(`minicpm4`: grouped-query attention without positional encoding whose
queries read the blocks an InfLLM-V2 selection over compressed keys
chooses) or a LINEAR layer (`lightning-attn`: one decayed (head_dim,
head_dim) state a head, rotary on q and k); in both a q/k RMSNorm over
the head's dims and an output gate read from the layer's normed input,
in a linear layer an output norm too; a SwiGLU MLP; the MiniCPM
family's scalings of the embedding, the residual branches and the
logits.

Keys `dims` reads, under their published names (config.json of
`openbmb/MiniCPM-SALA`): `hidden_size`, `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `intermediate_size`,
`num_hidden_layers`, `mixer_types` (read from entry
`first_layer_held`, a key of the file's own, for `num_hidden_layers`
entries: a configuration cut in depth keeps the published list whole
and says which stretch it holds), `lightning_nh`, `lightning_nkv`,
`lightning_head_dim`, `lightning_use_rope` (true), `attn_use_rope`
(false), `qk_norm` (true), `use_output_gate`, `use_output_norm`,
`attn_use_output_gate` (true), `hidden_act` (silu), `attention_bias`
(false), `tie_word_embeddings` (false), `rope_theta`, `rms_norm_eps`,
`scale_emb`, `scale_depth` (over the square root of the PUBLISHED
depth, `published.num_hidden_layers`), `dim_model_base`, `vocab_size`,
`max_position_embeddings`; and, of the file's own, `sparse_config`
(the MiniCPM4 family's selection sizes, which this config.json lacks:
`kernel_size`, `kernel_stride`, `block_size`, `topk`, `init_blocks`,
`window_size`, `dense_len`) and `lightning_slope_rate`. What the
program cannot be is refused.

Seeded f32 draws, layer by layer, for build.py and reference.py alone:
matrices normal / sqrt(fan_in), the embedding normal / sqrt(width),
the layer norms' gains 1, the q/k and output norms' gains 1 + normal /
10 (a gain of exactly 1 would leave their place in the order of
operations untested); no bias anywhere.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

KINDS = {"minicpm4": "attn", "lightning-attn": "linear"}
SELECT_KEYS = ("kernel_size", "kernel_stride", "block_size", "topk",
               "init_blocks", "window_size", "dense_len")


def dims(cfg: dict) -> dict:
    """The sizes, every value hashable (reference.py keys its compiled
    blocks by them)."""
    want = {"lightning_use_rope": True, "attn_use_rope": False,
            "qk_norm": True, "use_output_gate": True, "use_output_norm": True,
            "attn_use_output_gate": True, "hidden_act": "silu",
            "attention_bias": False, "tie_word_embeddings": False}
    for key, value in want.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]!r}: this family's block "
                             f"is {value!r}")
    layers, first = int(cfg["num_hidden_layers"]), int(cfg["first_layer_held"])
    names = cfg["mixer_types"][first:first + layers]
    if len(names) != layers or set(names) - set(KINDS):
        raise ValueError(f"mixer_types[{first}:{first + layers}] = {names}: "
                         f"want {layers} of {sorted(KINDS)}")
    heads, kv, hd = (int(cfg["num_attention_heads"]),
                     int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
    if heads % kv or hd % 2:
        raise ValueError(f"{heads} query heads over {kv} K/V heads of {hd}: "
                         "want a whole group and an even head width")
    if (int(cfg["lightning_nh"]), int(cfg["lightning_nkv"]),
            int(cfg["lightning_head_dim"])) != (heads, heads, hd):
        raise ValueError(
            "lightning heads " + str([cfg[k] for k in (
                "lightning_nh", "lightning_nkv", "lightning_head_dim")])
            + f": the program's linear layer has the model's {heads} heads "
            f"of {hd}, a key and a value head each")
    published = int(cfg.get("published", {}).get("num_hidden_layers", layers))
    return {
        "width": int(cfg["hidden_size"]), "heads": heads, "kv_heads": kv,
        "head_dim": hd, "mlp": int(cfg["intermediate_size"]),
        "layers": layers, "mixers": tuple(KINDS[n] for n in names),
        "select": tuple(int(cfg["sparse_config"][k]) for k in SELECT_KEYS),
        "slope": float(cfg["lightning_slope_rate"]),
        "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "emb_scale": float(cfg["scale_emb"]),
        "residual_scale": float(cfg["scale_depth"]) / math.sqrt(published),
        "logit_scale": float(cfg["dim_model_base"]) / int(cfg["hidden_size"]),
        "vocab": int(cfg["vocab_size"]),
        "max_seq": int(cfg["max_position_embeddings"]),
    }


def root_key(seed: int):
    return jax.random.key(int(seed))


def _dense(key, din, dout):
    return jax.random.normal(key, (din, dout), jnp.float32) / math.sqrt(din)


def _gain(width):
    return {"g": jnp.ones((width,), jnp.float32)}


def _drawn_gain(key, width):
    return {"g": 1.0 + jax.random.normal(key, (width,), jnp.float32) / 10.0}


def block_f32(dm: dict, key, i: int):
    """Layer i (a concrete index: its kind decides the tree) as an f32
    tree, matrices (in, out): `ln1`, `ln2` gains; `wq` (width, heads x
    head_dim); `wk`, `wv` (width, kv_heads x head_dim) in an "attn"
    layer, (width, heads x head_dim) in a "linear" one; `wo`; `wgate`
    (width, heads x head_dim); `q_norm`, `k_norm` {g (head_dim,)};
    in a linear layer `o_norm` {g (head_dim,)}: one gain for every
    head's dims; `wg`, `wu` (width, mlp), `wd` (mlp, width)."""
    w, hd, h = dm["width"], dm["head_dim"], dm["heads"]
    kv = h if dm["mixers"][i] == "linear" else dm["kv_heads"]
    k = jax.random.split(jax.random.fold_in(key, i + 1), 11)
    blk = {
        "ln1": _gain(w), "ln2": _gain(w),
        "wq": _dense(k[0], w, h * hd), "wk": _dense(k[1], w, kv * hd),
        "wv": _dense(k[2], w, kv * hd), "wo": _dense(k[3], h * hd, w),
        "wgate": _dense(k[4], w, h * hd),
        "q_norm": _drawn_gain(k[5], hd), "k_norm": _drawn_gain(k[6], hd),
        "wg": _dense(k[7], w, dm["mlp"]), "wu": _dense(k[8], w, dm["mlp"]),
        "wd": _dense(k[9], dm["mlp"], w),
    }
    if dm["mixers"][i] == "linear":
        blk["o_norm"] = _drawn_gain(k[10], hd)
    return blk


def top_f32(dm: dict, key):
    """The token embedding, the final norm, the untied head. There is
    no position table."""
    width, vocab = dm["width"], dm["vocab"]
    k = jax.random.split(jax.random.fold_in(key, 0), 2)
    return {
        "tok_emb": jax.random.normal(k[0], (vocab, width), jnp.float32)
        / math.sqrt(width),
        "ln_f": _gain(width),
        "head": _dense(k[1], width, vocab),
    }
