"""The system under test for a configuration of the GPT-2 family: the
program's `TransformerLM` (learned positions), seeded weights converted
block by block with the program's own quantizer, and a `PagedEngine`
over them.

The whole-model f32 tree never exists (StarCoderBase-7B's would be
28 GB): each block is drawn in f32 inside one jitted call
(weights.block_f32), cast or quantized there with
`ops/pallas_gemv.quantize_decode_params`, and only the converted form
comes out. The engine then takes the prepared tree as it is
(`weights_dtype="float32"` is its pass-through; `qmatmul` dispatches on
the leaf's type).

A configuration pins the model, the deployment (slots, max_len) and
the precision, and no engine tunable: prefill chunk, page size, paged
read, scheduler, prefix cache and speculation are the program's
defaults, read here from `PagedEngine`'s own signature so that a PR
which changes a default is measured with it.
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
        vocab=dm["vocab"], dim=dm["d"], heads=dm["heads"], depth=dm["depth"],
        max_seq=dm["max_seq"],
        kv_heads=0 if dm["n_kv"] == dm["heads"] else dm["n_kv"],
        pos="learned",
    )


def serving_params(dm: dict, seed: int, cfg: dict) -> dict:
    """The params tree the engine serves from, in the configuration's
    `weights_dtype`."""
    key = weights.root_key(seed)
    weights_dtype = cfg["weights_dtype"]

    @jax.jit
    def top(key):
        return quantize_decode_params(
            {**weights.top_f32(dm, key), "blocks": []}, weights_dtype)

    @jax.jit
    def block(key, i):
        # A one-block tree through the program's own conversion. Its
        # int8 branch (`ops/pallas_gemv.quantize_decode_params`)
        # quantizes `params["head"]` without asking whether there is
        # one, so a block alone needs some head to be let through: a
        # few zeros, quantized and dropped with the rest of this tree.
        # The real head is converted with the top, above.
        tree = {"head": jnp.zeros((8, 128), jnp.float32),
                "blocks": [weights.block_f32(dm, key, i)]}
        return quantize_decode_params(tree, weights_dtype)["blocks"][0]

    params = top(key)
    params["blocks"] = [block(key, i) for i in range(dm["depth"])]
    return params


def default_page_size() -> int:
    return inspect.signature(PagedEngine.__init__).parameters[
        "page_size"].default


def engine_of(cfg: dict, dm: dict, params) -> PagedEngine:
    """Pool sized to the deployment: every slot can hold max_len
    tokens (plus the scratch page), so nothing is ever preempted for
    pages; the page size is the program's default."""
    page = default_page_size()
    return PagedEngine(
        model_of(dm), params, slots=int(cfg["slots"]),
        num_pages=int(cfg["slots"]) * pages_for(int(cfg["max_len"]), page) + 1,
        cache_dtype=cfg["cache_dtype"], max_len=int(cfg["max_len"]),
        weights_dtype="float32",
    )
