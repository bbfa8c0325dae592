"""The system under test for a configuration of the `mla_moe` family:
the program's `TransformerLM` with `attn=LatentAttn(...)` and
`experts=RoutedExperts(...)`, seeded weights converted matrix by matrix
with the program's own `quantize_decode_params` — all but a layer's
`router`, which this module keeps out of the cast: it stays f32, a
choice among near-equal scores is not a matmul to round — and a
`PagedEngine` over them with the program's defaults for every tunable. The engine takes its page pool's layout —
one latent row a token a layer — from the model.

The program's tree for a block (models/generate.token_forward reads a
layer's kind off it): `ln1`, `ln2`, `q_norm`, `kv_norm` {g}; `wdq`,
`wdkv`, `wo`; `wuq_n` (heads, q_rank, nope) and `wuq_r` (heads, q_rank,
rope), the nope and the rope columns of the published q_b_proj; `wuk`
(heads, nope, kv_rank) and `wuv` (heads, kv_rank, v), the two halves of
kv_b_proj; then `wg`, `wu`, `wd` (a
dense layer) or `router`, `shared`, `experts` (an expert layer).
"""

from __future__ import annotations

import functools
import inspect

import jax

try:
    from mpi_cuda_cnn_tpu.models.transformer import (
        LatentAttn,
        RoutedExperts,
        TransformerLM,
    )
except ImportError as e:    # a program from before it could serve this
    raise SystemExit(
        "benchmarks/families/mla_moe: this checkout's program has no "
        f"latent attention or held-experts layer to serve ({e})") from e
from mpi_cuda_cnn_tpu.ops.pallas_gemv import quantize_decode_params
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
from mpi_cuda_cnn_tpu.serve.paged_cache import pages_for

from . import weights


def model_of(dm: dict) -> TransformerLM:
    factor, original, *rest = dm["yarn"]
    return TransformerLM(
        vocab=dm["vocab"], dim=dm["width"], heads=dm["heads"],
        depth=dm["layers"], max_seq=dm["max_seq"], pos="rope",
        norm_eps=dm["eps"],
        attn=LatentAttn(
            q_rank=dm["q_rank"], kv_rank=dm["kv_rank"], nope=dm["nope"],
            rope=dm["rope"], v=dm["v"], rope_theta=dm["rope_theta"],
            yarn=(factor, int(original), *rest)),
        experts=RoutedExperts(
            experts=dm["routed"], held=weights.held_ids(dm),
            top_k=dm["top_k"], groups=dm["groups"],
            top_groups=dm["top_groups"], scale=dm["route_scale"]),
    )


def program_block(dm: dict, blk: dict) -> dict:
    """The family's f32 block in the program's layout: q_b_proj parted
    into the heads' nope and rope columns, kv_b_proj into per-head key
    and value up-projections, all four head first."""
    blk = dict(blk)
    h, nope = dm["heads"], dm["nope"]
    wuq = blk.pop("wuq").reshape(dm["q_rank"], h, nope + dm["rope"])
    wukv = blk.pop("wukv").reshape(dm["kv_rank"], h, nope + dm["v"])
    return {**blk,
            "wuq_n": wuq[..., :nope].transpose(1, 0, 2),
            "wuq_r": wuq[..., nope:].transpose(1, 0, 2),
            "wuk": wukv[..., :nope].transpose(1, 2, 0),
            "wuv": wukv[..., nope:].transpose(1, 0, 2)}


def serving_params(dm: dict, seed: int, cfg: dict) -> dict:
    """The params tree the engine serves from, in the configuration's
    `weights_dtype`. No whole f32 layer on the way: a layer without its
    expert bank is one jitted call, each of the bank's three matrices
    one more."""
    if cfg["weights_dtype"] not in ("float32", "bfloat16"):
        raise ValueError(f"weights_dtype {cfg['weights_dtype']!r}: this "
                         "family serves float32 or bfloat16 weights")
    key = weights.root_key(seed)
    convert = functools.partial(quantize_decode_params,
                                dtype=cfg["weights_dtype"])
    top = jax.jit(lambda key: convert(
        {**weights.top_f32(dm, key), "blocks": []}))

    @functools.partial(jax.jit, static_argnums=2)
    def block(key, i, dense):
        blk = program_block(
            dm, weights.block_f32(dm, key, i, dense, experts=False))
        router = blk.pop("router", None)      # kept out of the cast
        blk = convert({"blocks": [blk]})["blocks"][0]
        if router is not None:
            blk["router"] = router
        return blk

    @functools.partial(jax.jit, static_argnums=2)
    def bank(key, i, name):
        return convert({"w": weights.expert_bank(dm, key, i, name)})["w"]

    params = top(key)
    for i in range(dm["layers"]):
        blk = block(key, i, i < dm["dense_layers"])
        if "router" in blk:
            blk["experts"] = {n: bank(key, i, n) for n in ("wg", "wu", "wd")}
        params["blocks"].append(blk)
    return params


def engine_of(cfg: dict, dm: dict, params) -> PagedEngine:
    """Every slot can hold `max_len` tokens (plus the scratch page);
    the page size is the program's default."""
    page = inspect.signature(PagedEngine.__init__).parameters[
        "page_size"].default
    return PagedEngine(
        model_of(dm), params, slots=int(cfg["slots"]),
        num_pages=int(cfg["slots"]) * pages_for(int(cfg["max_len"]), page) + 1,
        cache_dtype=cfg["cache_dtype"], max_len=int(cfg["max_len"]),
        weights_dtype="float32",
    )
