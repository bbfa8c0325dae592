"""The system under test for a configuration of the `sparse_linear`
family: the program's `TransformerLM` with a per-layer `mixers` list
("attn" layers that select the blocks they read, `select`; "linear"
layers that keep a state, `linear`), a `layout` that rotates the linear
layers only, and the three muP scales; seeded weights converted layer by
layer with the program's own `quantize_decode_params`; a `PagedEngine`
over them at the configuration's `prefill_chunk`, with the program's
defaults for every other tunable. The engine takes its page pools (K, V
and compressed keys of the "attn" layers) and the slots' states (the
"linear" layers', f32 whatever the cache's type) from the model.

The program's tree for a block (models/generate.token_forward reads a
layer's parts off it): `ln1`, `ln2` {g}; `wq` and `wkv` (keys then
values) in an "attn" layer, `wqkv` in a "linear" one; `q_norm`,
`k_norm` {g}; `wgate`; `wo`; `o_norm` {g (head_dim)} in a linear
layer; `wg`, `wu`, `wd`.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp

from mpi_cuda_cnn_tpu.models import transformer
from mpi_cuda_cnn_tpu.models.transformer import TransformerLM

_NEEDS = {"mixers", "select", "linear", "emb_scale", "residual_scale",
          "logit_scale"}
_lacks = sorted(_NEEDS - {f.name for f in dataclasses.fields(TransformerLM)})
if _lacks:      # a program from before it could serve this
    raise SystemExit(
        "benchmarks/families/sparse_linear: this checkout's program has no "
        "block selection, linear layers or muP scales to serve (no "
        "TransformerLM." + ", TransformerLM.".join(_lacks) + ")")
from mpi_cuda_cnn_tpu.ops.pallas_gemv import quantize_decode_params
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
from mpi_cuda_cnn_tpu.serve.paged_cache import pages_for

from . import weights


def model_of(dm: dict) -> TransformerLM:
    kernel, stride, block, topk, init, window, dense = dm["select"]
    return TransformerLM(
        vocab=dm["vocab"], dim=dm["width"], heads=dm["heads"],
        kv_heads=dm["kv_heads"], head_width=dm["head_dim"],
        depth=dm["layers"], max_seq=dm["max_seq"], pos="rope",
        norm_eps=dm["eps"], rope_theta=dm["rope_theta"],
        layout=tuple((m == "linear", False) for m in dm["mixers"]),
        mixers=dm["mixers"],
        select=transformer.SparseSelect(
            kernel=kernel, stride=stride, block=block, topk=topk,
            init_blocks=init, window=window, dense_len=dense),
        linear=(transformer.LinearAttn(slope=dm["slope"])
                if "linear" in dm["mixers"] else None),
        emb_scale=dm["emb_scale"], residual_scale=dm["residual_scale"],
        logit_scale=dm["logit_scale"],
    )


def program_block(dm: dict, blk: dict) -> dict:
    """The family's f32 block in the program's layout: an "attn"
    layer's keys and values projected by one matrix, a "linear"
    layer's queries, keys and values by one."""
    blk = dict(blk)
    q, k, v = blk.pop("wq"), blk.pop("wk"), blk.pop("wv")
    if "o_norm" in blk:
        return {**blk, "wqkv": jnp.concatenate([q, k, v], axis=1)}
    return {**blk, "wq": q, "wkv": jnp.concatenate([k, v], axis=1)}


def serving_params(dm: dict, seed: int, cfg: dict) -> dict:
    """The params tree the engine serves from, in the configuration's
    `weights_dtype`: a layer is one jitted call that draws and
    converts it, so no whole-model f32 tree exists."""
    if cfg["weights_dtype"] not in ("float32", "bfloat16"):
        raise ValueError(f"weights_dtype {cfg['weights_dtype']!r}: this "
                         "family serves float32 or bfloat16 weights")
    key = weights.root_key(seed)
    convert = functools.partial(quantize_decode_params,
                                dtype=cfg["weights_dtype"])
    top = jax.jit(lambda key: convert(
        {**weights.top_f32(dm, key), "blocks": []}))

    @functools.partial(jax.jit, static_argnums=1)
    def block(key, i):
        return convert({"blocks": [program_block(
            dm, weights.block_f32(dm, key, i))]})["blocks"][0]

    params = top(key)
    params["blocks"] = [block(key, i) for i in range(dm["layers"])]
    return params


def engine_of(cfg: dict, dm: dict, params) -> PagedEngine:
    """Every slot can hold `max_len` tokens (plus the scratch page);
    the chunk is the configuration's, the page size the program's
    default."""
    page = inspect.signature(PagedEngine.__init__).parameters[
        "page_size"].default
    return PagedEngine(
        model_of(dm), params, slots=int(cfg["slots"]),
        num_pages=int(cfg["slots"]) * pages_for(int(cfg["max_len"]), page) + 1,
        prefill_chunk=int(cfg["prefill_chunk"]),
        cache_dtype=cfg["cache_dtype"], max_len=int(cfg["max_len"]),
        weights_dtype="float32",
    )
