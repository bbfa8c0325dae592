"""The system under test for a configuration of this family: the
program's `TransformerLM(pos="rope", kv_heads=...)`, seeded weights
converted block by block with the program's own quantizer, and a
`PagedEngine` over them with the program's defaults for every tunable.
"""

from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp

from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
from mpi_cuda_cnn_tpu.ops.pallas_gemv import quantize_decode_params
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
from mpi_cuda_cnn_tpu.serve.paged_cache import pages_for

from . import weights


def model_of(dm: dict) -> TransformerLM:
    return TransformerLM(
        vocab=dm["vocab"], dim=dm["width"], heads=dm["q_heads"],
        depth=dm["layers"], max_seq=dm["max_seq"], kv_heads=dm["kv_heads"],
        pos="rope",
    )


def serving_params(dm: dict, seed: int, cfg: dict) -> dict:
    """The params tree the engine serves from, in the configuration's
    `weights_dtype`; no whole-model f32 tree on the way."""
    key = weights.root_key(seed)
    weights_dtype = cfg["weights_dtype"]

    @jax.jit
    def top(key):
        return quantize_decode_params(
            {**weights.top_f32(dm, key), "blocks": []}, weights_dtype)

    @jax.jit
    def block(key, i):
        # `quantize_decode_params`' int8 branch quantizes a head without
        # asking whether there is one: a few zeros let a lone block in.
        tree = {"head": jnp.zeros((8, 128), jnp.float32),
                "blocks": [weights.block_f32(dm, key, i)]}
        return quantize_decode_params(tree, weights_dtype)["blocks"][0]

    params = top(key)
    params["blocks"] = [block(key, i) for i in range(dm["layers"])]
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
