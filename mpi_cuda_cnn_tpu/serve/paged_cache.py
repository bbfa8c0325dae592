"""Paged KV cache — virtual memory for decode (PagedAttention, Kwon et
al., SOSP '23; layout per the TPU paged-attention kernel notes).

The contiguous cache (models/generate.init_cache) sizes every sequence
at max_seq: a batch of B requests pins B * max_seq * Hkv * hd * 2 cache
bytes per layer no matter how short each request actually is, finished
sequences hold their extent until the whole batch drains, and a new
request cannot be admitted mid-flight because the buffers are indexed by
batch row. PERF.md's decode table shows tokens/s tracks cache bytes
almost linearly — so idle cache extent is directly lost throughput.

This module replaces the per-sequence extent with FIXED-SIZE TOKEN PAGES
in one global pool:

- per layer, `k`/`v` pools of shape (num_pages, page_size, Hkv, hd)
  (+ f32 absmax scales (num_pages, page_size, Hkv, 1) for the int8
  form — the same quantization contract as the contiguous cache);
- a per-slot BLOCK TABLE (slots, pages_per_slot) of page indices maps a
  sequence's logical positions to physical pages — position p lives in
  page block_table[s, p // page_size] at offset p % page_size;
- PAGE 0 IS RESERVED as a scratch page: host-side invariants route every
  write from a dead slot or a padding token there, so a freed page can
  be re-issued to another sequence without a stale writer corrupting it.

The device-side ops are pure functions of (pages, block_table): the
scatter write + gathered read (`paged_update_attend`) and the
generate-compatible forward (`paged_decode_block` — models/generate's
decode_step/decode_block accept a PagedKVCache and land here). The
attention read itself is models/generate.attend_kv, shared with the
contiguous path — the parity tests rest on the two layouts differing
only in how cache rows are materialized, never in the attention math.
Host-side page accounting (alloc/free/ownership) is `PagePool`; policy
(who gets pages when) lives in scheduler.py.

TPU note: the gather materializes (B, L, Hkv, hd) rows per layer — the
XLA formulation of the paged read. The fused form is
ops/pallas_paged_attention.paged_attend (ISSUE 12; first compiled for
the v5e in PR 21): pages stream HBM -> VMEM behind scalar-prefetched
block tables with the Pallas pipeline double-buffering the per-page
copies, and the gathered rows never exist outside VMEM.
`paged_update_attend(kernel="pallas")` dispatches to it (the write
stays shared); PagedKVCache carries the choice as static metadata so
one engine never mixes layouts. Parity vs this gather: a few f32 ulp
in f32 and int8, bf16's probability rounding in bf16
(tests/test_paged_kernel.py in interpret mode on CPU; chip_smoke.py on
the chip). Which read is faster is not measured (ROADMAP S4).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import (
    _quant_kv,
    attend_kv,
    attend_latent,
    token_forward,
)
from ..models.transformer import TransformerLM

# Host-side page accounting lives in pool.py (jax-free — the policy
# layer imports it without pulling this module's device stack);
# re-exported here so device-side callers keep one import surface.
from .pool import PagePool, pages_for  # noqa: F401


@dataclasses.dataclass
class PagedKVCache:
    """Device-side paged cache state: per-layer page pools + the block
    table mapping each slot's logical positions to physical pages.
    `page_size` is static metadata (it shapes the compiled program), as
    is `kernel` — "gather" (the XLA formulation) or "pallas" (the fused
    ops/pallas_paged_attention read); carrying the choice on the cache
    keeps ONE decode implementation with a leaf-level dispatch, the
    QuantW pattern applied to the attention read."""

    pages: list[dict]
    block_table: jnp.ndarray      # (slots, pages_per_slot) int32
    page_size: int
    kernel: str = "gather"
    # What the forward that produced this cache counted, for the tick
    # record: int32 [expert pairs computed, held experts hit, largest
    # expert load, latent rows the read touched]; None for a model
    # with neither experts nor latent rows (paged_forward).
    counts: jnp.ndarray | None = None

    @property
    def num_pages(self) -> int:
        return next(iter(self.pages[0].values())).shape[0]

    @property
    def slots(self) -> int:
        return self.block_table.shape[0]


jax.tree_util.register_dataclass(
    PagedKVCache, data_fields=["pages", "block_table", "counts"],
    meta_fields=["page_size", "kernel"],
)

_KERNELS = ("gather", "pallas")


def init_paged_cache(model: TransformerLM, *, slots: int, num_pages: int,
                     page_size: int, dtype=jnp.float32,
                     max_len: int | None = None,
                     kernel: str = "gather") -> PagedKVCache:
    """Empty page pools + an all-scratch block table.

    num_pages INCLUDES the reserved scratch page 0, so num_pages - 1
    pages are allocatable; max_len (default model.max_seq) bounds any
    one sequence and fixes the block-table width. Total cache bytes are
    num_pages * page_size tokens per layer — the pool is sized to the
    MEMORY BUDGET, not to slots * max_seq (the contiguous cache's
    forced extent; the whole point of paging).
    """
    if num_pages < 2:
        raise ValueError(f"num_pages {num_pages} < 2 (page 0 is scratch)")
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    if kernel not in _KERNELS:
        raise ValueError(f"kernel {kernel!r}: want one of {_KERNELS}")
    max_len = max_len or model.max_seq
    table = jnp.zeros((slots, pages_for(max_len, page_size)), jnp.int32)
    if model.attn is not None:
        # The LATENT layout (transformer.LatentAttn): one pool a layer,
        # one row a token — the compressed latent and the shared rotary
        # key, written once, zero lanes up to a whole lane tile
        # (latent_row_lanes) — and no V pool. The model chooses it;
        # every page operation that copies pools by name (copy-on-
        # write, handoff, spill, readmit) moves these rows as it moves
        # K/V pages.
        if jnp.dtype(dtype) == jnp.int8 or kernel != "gather":
            raise ValueError(
                "a latent-attention model's page pool is float32 or "
                "bfloat16 rows read by the 'gather' formulation; got "
                f"cache dtype {jnp.dtype(dtype).name}, kernel {kernel!r}")
        pages = [{"c": jnp.zeros(
            (num_pages, page_size, latent_row_lanes(model.attn)), dtype)}
            for _ in range(model.depth)]
        return PagedKVCache(pages=pages, block_table=table,
                            page_size=page_size, kernel=kernel)
    shape = (num_pages, page_size, model.n_kv, model.head_dim)
    int8 = jnp.dtype(dtype) == jnp.int8
    sshape = shape[:-1] + (1,)
    pages = []
    for _ in range(model.depth):
        if int8:
            pages.append({
                "k": jnp.zeros(shape, jnp.int8),
                "ks": jnp.zeros(sshape, jnp.float32),
                "v": jnp.zeros(shape, jnp.int8),
                "vs": jnp.zeros(sshape, jnp.float32),
            })
        else:
            pages.append({"k": jnp.zeros(shape, dtype),
                          "v": jnp.zeros(shape, dtype)})
    return PagedKVCache(pages=pages, block_table=table,
                        page_size=page_size, kernel=kernel)


_LANES = 128    # the TPU's lane tile: a row's stride in a pool


def latent_row_lanes(attn) -> int:
    """The stored width of a latent row: its kv_rank + rope values and
    zero lanes up to a multiple of the lane tile. A row-major pool is
    padded to that stride on the TPU whatever its logical width; a
    logical width that is NOT a multiple (576) makes the runtime's
    default layout put the PAGE axis on the lanes instead, and every
    program then transposes the whole pool in and out around its
    scatter (measured: 12 of 56 ms an iteration, PERF.md section 6,
    PR 28). Spelling the padding out keeps the pool row-major."""
    return -(-attn.row // _LANES) * _LANES


def _write_index(positions, valid, block_table, page_size: int):
    """Flat (page, offset) of every token's cache row; an invalid token
    (padding, a dead slot) is sent to scratch page 0, offset 0."""
    page_idx = jnp.take_along_axis(block_table, positions // page_size,
                                   axis=1)                  # (B, kk)
    page_idx = jnp.where(valid, page_idx, 0)
    off = jnp.where(valid, positions % page_size, 0)
    return page_idx.reshape(-1), off.reshape(-1)


def paged_update_attend_latent(c: dict, q, row, positions, valid,
                               block_table, page_size: int, blk: dict,
                               attn):
    """paged_update_attend for the latent layout: the token's one row
    (B, kk, 1, kv_rank + rope) is written to pool `c` (zero lanes
    after it, latent_row_lanes), then every
    slot's pages are gathered into (B, L, row) and read by
    generate.attend_latent with the block's up-projections `wuk`/`wuv`.
    Returns (o: (B, kk, H*v) f32, new_c, rows the read touched)."""
    b, kk = positions.shape
    pi, of = _write_index(positions, valid, block_table, page_size)
    row = row.astype(c["c"].dtype).reshape(b * kk, -1)
    pool = c["c"].at[pi, of].set(
        jnp.pad(row, ((0, 0), (0, c["c"].shape[-1] - row.shape[-1]))))
    length = block_table.shape[1] * page_size
    rows = pool[block_table].reshape(b, length, -1)
    mask = jnp.arange(length)[None, None, :] <= positions[:, :, None]
    o = attend_latent(q, rows, mask, blk["wuk"], blk["wuv"], attn)
    return o, {"c": pool}, b * length


def paged_update_attend(c: dict, q, k, v, positions, valid, block_table,
                        page_size: int, kernel: str = "gather"):
    """One layer's paged write + attention read.

    q: (B, kk, H, hd); k/v: (B, kk, Hkv, hd); positions: (B, kk)
    absolute positions; valid: (B, kk) bool — invalid tokens (padding
    beyond a prompt's length, dead slots) write to scratch page 0 at
    offset 0 instead, so they can never touch a page owned by a live
    sequence. Writes land FIRST (in-chunk causality: row i then reads
    rows <= i through the read), then the read runs per `kernel`:
    "gather" materializes each slot's pages into (B, L, Hkv, hd) rows
    for the shared attend_kv read; "pallas" streams the same pages
    HBM -> VMEM inside ops/pallas_paged_attention.paged_attend (equal
    to the gather within rounding). Either way the read is
    masked to key positions <= the row's own position; positions beyond
    a slot's written extent read whatever the (possibly scratch/stale)
    rows hold — the mask keeps them out of the softmax.
    Returns (o: (B, kk, H*hd) f32, new_c).
    """
    b, kk = positions.shape
    hkv, hd = k.shape[2], k.shape[3]
    pi, of = _write_index(positions, valid, block_table, page_size)
    int8 = c["k"].dtype == jnp.int8
    if int8:
        qk8, sk8 = _quant_kv(k)
        qv8, sv8 = _quant_kv(v)
        new_c = {
            "k": c["k"].at[pi, of].set(qk8.reshape(b * kk, hkv, hd)),
            "ks": c["ks"].at[pi, of].set(sk8.reshape(b * kk, hkv, 1)),
            "v": c["v"].at[pi, of].set(qv8.reshape(b * kk, hkv, hd)),
            "vs": c["vs"].at[pi, of].set(sv8.reshape(b * kk, hkv, 1)),
        }
    else:
        cdt = c["k"].dtype
        new_c = {
            "k": c["k"].at[pi, of].set(
                k.astype(cdt).reshape(b * kk, hkv, hd)),
            "v": c["v"].at[pi, of].set(
                v.astype(cdt).reshape(b * kk, hkv, hd)),
        }
    if kernel == "pallas":
        from ..ops.pallas_paged_attention import paged_attend

        o = paged_attend(q, new_c, positions, block_table, page_size)
        return o, new_c
    # Gather this slot's pages into contiguous logical rows. L =
    # pages_per_slot * page_size — the engine sizes the table to the
    # serving max_len, not to the pool (reads scale with the SEQUENCE
    # bound; pool size only bounds total residency).
    npages = block_table.shape[1]
    gathered = {
        name: new_c[name][block_table].reshape(
            b, npages * page_size, *new_c[name].shape[2:]
        )
        for name in new_c
    }
    mask = (jnp.arange(npages * page_size)[None, None, :]
            <= positions[:, :, None])         # (B, kk, L)
    o = attend_kv(q, gathered["k"], gathered["v"], mask,
                  cks=gathered.get("ks"), cvs=gathered.get("vs"))
    return o, new_c


def paged_forward(model: TransformerLM, params, toks, positions, valid,
                  cache: PagedKVCache):
    """toks (B, kk) through the model against the paged cache — the
    paged twin of decode_block's contiguous path, same token_forward
    skeleton, attend swapped (by the pool's layout: K/V heads, or
    latent rows where the model has latent attention).
    positions/valid: (B, kk).
    Returns (logits (B, kk, vocab) f32, new PagedKVCache); the new
    cache carries this forward's `counts` where the model has expert
    layers or latent rows."""
    new_pages: list[dict] = []
    rows_read = 0

    def attend(i, q, k, v):
        nonlocal rows_read
        if model.attn is not None:
            o, new_c, n = paged_update_attend_latent(
                cache.pages[i], q, k, positions, valid, cache.block_table,
                cache.page_size, params["blocks"][i], model.attn)
            rows_read += n
        else:
            o, new_c = paged_update_attend(
                cache.pages[i], q, k, v, positions, valid,
                cache.block_table, cache.page_size, kernel=cache.kernel,
            )
        new_pages.append(new_c)
        return o

    logits, counts = token_forward(model, params, toks, positions, attend,
                                   valid)
    if counts is not None or rows_read:
        counts = jnp.concatenate([
            jnp.zeros((3,), jnp.int32) if counts is None else counts,
            jnp.full((1,), rows_read, jnp.int32)])
    return logits, dataclasses.replace(cache, pages=new_pages, counts=counts)


def paged_decode_block(model: TransformerLM, params, toks, pos,
                       cache: PagedKVCache):
    """The generate-surface adapter: decode_step/decode_block semantics
    over a PagedKVCache. pos may be a scalar start (all rows at the same
    depth, the static-batch form) or a (B,) per-slot vector (the
    continuous-batching form). All tokens are valid writes — padding /
    dead-slot routing is the engine's concern (paged_forward + explicit
    `valid`). Concrete out-of-range positions raise, mirroring the
    contiguous path's guard — past the block-table extent the gathered
    page index would CLAMP to the last column and silently scatter over
    the sequence's final legitimate cache rows (traced positions cannot
    be checked, exactly as in contiguous decode_block).
    Returns (logits (B, k, vocab), new cache)."""
    b, kk = toks.shape
    limit = cache.block_table.shape[1] * cache.page_size
    if not isinstance(pos, jax.core.Tracer):
        hi = int(np.max(np.asarray(pos))) + kk
        if hi > limit:
            raise ValueError(
                f"block reaching position {hi} out of range (block table "
                f"covers {limit} = {cache.block_table.shape[1]} pages x "
                f"{cache.page_size})"
            )
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        positions = jnp.broadcast_to(pos + jnp.arange(kk), (b, kk))
    else:
        positions = pos[:, None] + jnp.arange(kk)[None, :]
    logits, cache = paged_forward(
        model, params, toks, positions, jnp.ones((b, kk), bool), cache
    )
    return logits, cache
