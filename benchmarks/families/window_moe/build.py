"""The system under test for a configuration of the `window_moe` family:
the program's `TransformerLM` with a head width of its own, a per-layer
`layout` (rotary or none, window or none) and `experts=RoutedExperts(
router="softmax", act="relu", reads="layer_input")` holding every
expert, seeded weights converted matrix by matrix with the program's own
`quantize_decode_params` — all but a layer's `router`, which this
module keeps out of the cast: it stays f32, a choice among near-equal
probabilities is not a matmul to round — and a `PagedEngine` over them
at the configuration's `prefill_chunk`, with the program's defaults for
every other tunable. The engine takes its two layer groups (global and
windowed: a page pool and a block table each) from the model.

The program's tree for a block (models/generate.token_forward reads a
layer's kind off it): `ln1`, `ln2` {g}; `wq` (width, heads x head_dim),
`wkv` (width, 2 x kv_heads x head_dim: keys then values), `wo`;
`router` {gate}; `experts` {wg, wu, wd}. No `shared`.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp

from mpi_cuda_cnn_tpu.models.transformer import RoutedExperts, TransformerLM

_NEEDS = {RoutedExperts: {"router", "act", "reads"},
          TransformerLM: {"head_width", "layout", "rope_theta", "window"}}
_lacks = sorted(f"{cls.__name__}.{name}" for cls, names in _NEEDS.items()
                for name in names - {f.name for f in dataclasses.fields(cls)})
if _lacks:      # a program from before it could serve this
    raise SystemExit(
        "benchmarks/families/window_moe: this checkout's program has no "
        "per-layer windows, head width or softmax router to serve (no "
        + ", ".join(_lacks) + ")")
from mpi_cuda_cnn_tpu.ops.pallas_gemv import quantize_decode_params
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
from mpi_cuda_cnn_tpu.serve.paged_cache import pages_for

from . import weights


def model_of(dm: dict) -> TransformerLM:
    return TransformerLM(
        vocab=dm["vocab"], dim=dm["width"], heads=dm["heads"],
        kv_heads=dm["kv_heads"], head_width=dm["head_dim"],
        depth=dm["layers"], max_seq=dm["max_seq"], pos="rope",
        norm_eps=dm["eps"], rope_theta=dm["rope_theta"],
        window=dm["window"] if any(dm["window_layout"]) else 0,
        layout=tuple((bool(r), bool(w)) for r, w in
                     zip(dm["rope_layout"], dm["window_layout"])),
        experts=RoutedExperts(
            experts=dm["experts"], held=tuple(range(dm["experts"])),
            top_k=dm["top_k"], router="softmax", act="relu",
            reads="layer_input"),
    )


def program_block(blk: dict) -> dict:
    """The family's f32 block in the program's layout: keys and values
    projected by one matrix."""
    blk = dict(blk)
    return {**blk, "wkv": jnp.concatenate([blk.pop("wk"), blk.pop("wv")],
                                          axis=1)}


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

    @jax.jit
    def block(key, i):
        blk = program_block(weights.block_f32(dm, key, i, experts=False))
        router = blk.pop("router")            # kept out of the cast
        blk = convert({"blocks": [blk]})["blocks"][0]
        return {**blk, "router": router}

    @functools.partial(jax.jit, static_argnums=2)
    def bank(key, i, name):
        return convert({"w": weights.expert_bank(dm, key, i, name)})["w"]

    params = top(key)
    for i in range(dm["layers"]):
        blk = block(key, i)
        blk["experts"] = {n: bank(key, i, n) for n in weights.BANK}
        params["blocks"].append(blk)
    return params


def engine_of(cfg: dict, dm: dict, params) -> PagedEngine:
    """Every slot can hold `max_len` tokens in the global group (plus
    the scratch page); the engine sizes the windowed group to full
    coverage itself (window + chunk rows a slot); the chunk is the
    configuration's, the page size the program's default."""
    page = inspect.signature(PagedEngine.__init__).parameters[
        "page_size"].default
    return PagedEngine(
        model_of(dm), params, slots=int(cfg["slots"]),
        num_pages=int(cfg["slots"]) * pages_for(int(cfg["max_len"]), page) + 1,
        prefill_chunk=int(cfg["prefill_chunk"]),
        cache_dtype=cfg["cache_dtype"], max_len=int(cfg["max_len"]),
        weights_dtype="float32",
    )
