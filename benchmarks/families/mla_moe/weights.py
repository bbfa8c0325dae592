"""The `mla_moe` family: a DeepSeek-V3-shaped decoder (the language
model of dots.vlm1.inst) as ONE CHIP of a stage holds it — multi-head
latent attention whole, the shared expert whole, `n_routed_experts`
HELD experts of the `published.n_routed_experts` the router scores,
and a slice of the vocabulary.

Keys `dims` reads, under their published names (config.json of
`DeepseekV3ForCausalLM`-shaped models): `hidden_size`,
`num_attention_heads` (= `num_key_value_heads`), `q_lora_rank`,
`kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`,
`num_hidden_layers`, `first_k_dense_replace` (leading dense layers),
`moe_layer_freq` (1), `intermediate_size` (dense SwiGLU width),
`moe_intermediate_size` (an expert's width), `n_shared_experts` (the
shared expert is one SwiGLU of that many expert widths),
`n_routed_experts` (the experts HELD here; listed in `reduced`),
`published.n_routed_experts` (the router's width, all experts of the
layer), `first_held_expert` (this file's own key: the held ids are that
many consecutive ids from it), `num_experts_per_tok`, `n_group`,
`topk_group`, `routed_scaling_factor`, `norm_topk_prob` (true),
`scoring_func` (sigmoid), `topk_method` (noaux_tc), `hidden_act`
(silu), `rms_norm_eps`, `rope_theta`, `rope_scaling` (type yarn:
`factor`, `original_max_position_embeddings`, `beta_fast`, `beta_slow`,
`mscale`, `mscale_all_dim`), `max_position_embeddings`, `vocab_size`
(the rows held: the slice), `attention_bias` (false),
`tie_word_embeddings` (false), `num_nextn_predict_layers` (0: the MTP
module is not held). What the program cannot be is refused.

Seeded f32 draws, block by block, for build.py and reference.py alone:
matrices normal / sqrt(fan_in), the embedding normal / sqrt(width), RMS
gains 1, the router's `e_score_correction_bias` 0.01 x normal. Expert e
of layer i is drawn from (seed, i, e) alone, so every share of a layer
draws the same expert whichever others it holds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_YARN = ("factor", "original_max_position_embeddings", "beta_fast",
         "beta_slow", "mscale", "mscale_all_dim")


def dims(cfg: dict) -> dict:
    """The sizes, every value hashable (reference.py keys its compiled
    blocks by them)."""
    heads = int(cfg["num_attention_heads"])
    want = {"num_key_value_heads": heads, "moe_layer_freq": 1,
            "norm_topk_prob": True, "scoring_func": "sigmoid",
            "topk_method": "noaux_tc", "hidden_act": "silu",
            "attention_bias": False, "tie_word_embeddings": False,
            "num_nextn_predict_layers": 0}
    for key, value in want.items():
        if cfg[key] != value:
            raise ValueError(f"{key} {cfg[key]!r}: this family's block "
                             f"is {value!r}")
    scaling = cfg["rope_scaling"]
    if scaling["type"] != "yarn":
        raise ValueError(f"rope_scaling type {scaling['type']!r}: yarn")
    if int(cfg["qk_rope_head_dim"]) % 2:
        raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
    routed = int(cfg["published"].get("n_routed_experts",
                                      cfg["n_routed_experts"]))
    held, first = int(cfg["n_routed_experts"]), int(cfg["first_held_expert"])
    groups = int(cfg["n_group"])
    if routed % groups or not 0 <= first <= routed - held:
        raise ValueError(f"{routed} routed experts: {groups} groups must "
                         f"divide them and ids {first}..{first + held - 1} "
                         "lie among them")
    layers, dense = (int(cfg["num_hidden_layers"]),
                     int(cfg["first_k_dense_replace"]))
    if not 0 <= dense <= layers:
        raise ValueError(f"first_k_dense_replace {dense} of {layers} layers")
    return {
        "width": int(cfg["hidden_size"]), "heads": heads,
        "q_rank": int(cfg["q_lora_rank"]), "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "layers": layers, "dense_layers": dense,
        "dense_mlp": int(cfg["intermediate_size"]),
        "expert_mlp": int(cfg["moe_intermediate_size"]),
        "shared_mlp": int(cfg["moe_intermediate_size"])
        * int(cfg["n_shared_experts"]),
        "routed": routed, "held": held, "first_held": first,
        "top_k": int(cfg["num_experts_per_tok"]), "groups": groups,
        "top_groups": int(cfg["topk_group"]),
        "route_scale": float(cfg["routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"]),
        "rope_theta": float(cfg["rope_theta"]),
        "yarn": tuple(float(scaling[k]) for k in _YARN),
        "vocab": int(cfg["vocab_size"]),
        "max_seq": int(cfg["max_position_embeddings"]),
    }


def held_ids(dm: dict) -> tuple[int, ...]:
    return tuple(range(dm["first_held"], dm["first_held"] + dm["held"]))


def root_key(seed: int):
    return jax.random.key(int(seed))


def _dense(key, din, dout):
    return jax.random.normal(key, (din, dout), jnp.float32) / math.sqrt(din)


def _gain(width):
    return {"g": jnp.ones((width,), jnp.float32)}


def _swiglu(key, width, mlp):
    k = jax.random.split(key, 3)
    return {"wg": _dense(k[0], width, mlp), "wu": _dense(k[1], width, mlp),
            "wd": _dense(k[2], mlp, width)}


def _layer_keys(key, i):
    return jax.random.split(jax.random.fold_in(key, i + 1), 10)


def expert_bank(dm: dict, key, i, name: str, ids=None):
    """One matrix (`wg`, `wu` or `wd`) of layer i's experts `ids`
    (default: the held ones), stacked (len(ids), din, dout)."""
    k = _layer_keys(key, i)[7 + ("wg", "wu", "wd").index(name)]
    width, mlp = dm["width"], dm["expert_mlp"]
    din, dout = (mlp, width) if name == "wd" else (width, mlp)
    ids = jnp.asarray(held_ids(dm) if ids is None else ids, jnp.int32)
    return jax.vmap(lambda e: _dense(jax.random.fold_in(k, e), din, dout))(ids)


def block_f32(dm: dict, key, i, dense: bool, experts: bool = True):
    """Layer i (a traced or concrete index; `dense` = i <
    dm["dense_layers"], static: it decides the layer's kind) as an f32
    tree. `wdq`/`wuq` are q_a_proj/q_b_proj, `wdkv` kv_a_proj_with_mqa
    (latent then rotary key), `wukv` kv_b_proj (kv_rank, heads x (nope
    + v)), `wo` o_proj, all (in, out); an expert layer has `router`
    {gate (width, routed), bias}, `shared`, and with `experts` the held
    bank {wg, wu, wd}."""
    w, h = dm["width"], dm["heads"]
    k = _layer_keys(key, i)
    blk = {
        "ln1": _gain(w), "ln2": _gain(w),
        "wdq": _dense(k[0], w, dm["q_rank"]), "q_norm": _gain(dm["q_rank"]),
        "wuq": _dense(k[1], dm["q_rank"], h * (dm["nope"] + dm["rope"])),
        "wdkv": _dense(k[2], w, dm["kv_rank"] + dm["rope"]),
        "kv_norm": _gain(dm["kv_rank"]),
        "wukv": _dense(k[3], dm["kv_rank"], h * (dm["nope"] + dm["v"])),
        "wo": _dense(k[4], h * dm["v"], w),
    }
    if dense:
        return {**blk, **_swiglu(k[5], w, dm["dense_mlp"])}
    kg, kb = jax.random.split(k[5])
    blk["router"] = {
        "gate": _dense(kg, w, dm["routed"]),
        "bias": 0.01 * jax.random.normal(kb, (dm["routed"],), jnp.float32)}
    blk["shared"] = _swiglu(k[6], w, dm["shared_mlp"])
    if experts:
        blk["experts"] = {n: expert_bank(dm, key, i, n)
                          for n in ("wg", "wu", "wd")}
    return blk


def top_f32(dm: dict, key):
    """The token embedding (the slice's rows), the final norm, the head
    over the slice. There is no position table."""
    width, vocab = dm["width"], dm["vocab"]
    k = jax.random.split(jax.random.fold_in(key, 0), 2)
    return {
        "tok_emb": jax.random.normal(k[0], (vocab, width), jnp.float32)
        / math.sqrt(width),
        "ln_f": _gain(width),
        "head": _dense(k[1], width, vocab),
    }
