"""The `window_moe` family: a SmallThinker-shaped decoder — grouped-query
attention whose head width is a key of its own (heads x head_dim need
not be the hidden size), layers that are either GLOBAL with no
positional encoding at all or SLIDING-WINDOW with rotary, by two
per-layer lists, and in every layer a softmax top-k router over small
ReGLU experts, all of them held here, with no shared expert.

Keys `dims` reads, under their published names (config.json of
`SmallThinkerForCausalLM`): `hidden_size`, `num_attention_heads`,
`num_key_value_heads`, `head_dim`, `num_hidden_layers`, `rope_layout`
(per layer: 1 = rotary, 0 = none) and `sliding_window_layout` (per
layer: 1 = windowed, 0 = global), of which the first
`num_hidden_layers` entries are read (a configuration cut in depth
keeps the published lists whole),
`sliding_window_size`, `rope_theta`, `rope_scaling` (null),
`rms_norm_eps`,
`moe_ffn_hidden_size` (an expert's width), `moe_num_primary_experts`,
`moe_num_active_primary_experts` (experts a token),
`moe_primary_router_apply_softmax` (true: softmax over all experts,
then the top k), `norm_topk_prob` (true: the chosen weights sum to 1),
`vocab_size`, `max_position_embeddings`, `tie_word_embeddings` (false).
What the program cannot be is refused.

Seeded f32 draws, layer by layer, for build.py and reference.py alone:
matrices normal / sqrt(fan_in), the embedding normal / sqrt(width), RMS
gains 1; there is no bias anywhere. Expert e of layer i is drawn from
(seed, i, e) alone.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BANK = ("wg", "wu", "wd")


def dims(cfg: dict) -> dict:
    """The sizes, every value hashable (reference.py keys its compiled
    blocks by them)."""
    want = {"moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
            "tie_word_embeddings": False}
    if cfg.get("rope_scaling") is not None:
        raise ValueError(f"rope_scaling {cfg['rope_scaling']!r}: this "
                         "family's rotary is unscaled")
    for key, value in want.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]!r}: this family's block "
                             f"is {value!r}")
    layers = int(cfg["num_hidden_layers"])
    rope, windowed = (tuple(int(v) for v in cfg[name][:layers]) for name in
                      ("rope_layout", "sliding_window_layout"))
    for name, layout in (("rope_layout", rope),
                         ("sliding_window_layout", windowed)):
        if len(layout) != layers or set(layout) - {0, 1}:
            raise ValueError(f"{name} {cfg[name]}: want at least {layers} "
                             "entries of 0/1")
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    if heads % kv or int(cfg["head_dim"]) % 2:
        raise ValueError(f"{heads} query heads over {kv} K/V heads of "
                         f"{cfg['head_dim']}: want a whole group and an "
                         "even head width (rotary pairs)")
    experts, top_k = (int(cfg["moe_num_primary_experts"]),
                      int(cfg["moe_num_active_primary_experts"]))
    if not 0 < top_k <= experts:
        raise ValueError(f"top {top_k} of {experts} experts")
    return {
        "width": int(cfg["hidden_size"]), "heads": heads, "kv_heads": kv,
        "head_dim": int(cfg["head_dim"]), "layers": layers,
        "rope_layout": rope, "window_layout": windowed,
        "window": int(cfg["sliding_window_size"]),
        "rope_theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "expert_mlp": int(cfg["moe_ffn_hidden_size"]),
        "experts": experts, "top_k": top_k,
        "vocab": int(cfg["vocab_size"]),
        "max_seq": int(cfg["max_position_embeddings"]),
    }


def root_key(seed: int):
    return jax.random.key(int(seed))


def _dense(key, din, dout):
    return jax.random.normal(key, (din, dout), jnp.float32) / math.sqrt(din)


def _gain(width):
    return {"g": jnp.ones((width,), jnp.float32)}


def _layer_keys(key, i):
    return jax.random.split(jax.random.fold_in(key, i + 1), 8)


def expert_bank(dm: dict, key, i, name: str):
    """One matrix (`wg`, `wu` or `wd`) of all of layer i's experts,
    stacked (experts, din, dout)."""
    k = _layer_keys(key, i)[5 + BANK.index(name)]
    width, mlp = dm["width"], dm["expert_mlp"]
    din, dout = (mlp, width) if name == "wd" else (width, mlp)
    return jax.vmap(lambda e: _dense(jax.random.fold_in(k, e), din, dout))(
        jnp.arange(dm["experts"], dtype=jnp.int32))


def block_f32(dm: dict, key, i, experts: bool = True):
    """Layer i (a traced or concrete index) as an f32 tree: `ln1`,
    `ln2` gains; `wq` (width, heads x head_dim), `wk`, `wv` (width,
    kv_heads x head_dim), `wo` (heads x head_dim, width), all (in,
    out); `router` {gate (width, experts)}; with `experts` the bank
    {wg, wu: (experts, width, mlp), wd: (experts, mlp, width)}. Which
    layers rotate and which are windowed is dims' to say, not the
    tree's."""
    w, hd = dm["width"], dm["head_dim"]
    k = _layer_keys(key, i)
    blk = {
        "ln1": _gain(w), "ln2": _gain(w),
        "wq": _dense(k[0], w, dm["heads"] * hd),
        "wk": _dense(k[1], w, dm["kv_heads"] * hd),
        "wv": _dense(k[2], w, dm["kv_heads"] * hd),
        "wo": _dense(k[3], dm["heads"] * hd, w),
        "router": {"gate": _dense(k[4], w, dm["experts"])},
    }
    if experts:
        blk["experts"] = {n: expert_bank(dm, key, i, n) for n in BANK}
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
