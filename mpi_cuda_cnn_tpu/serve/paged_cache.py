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
scatter write + the read (`paged_update_attend`) and the
generate-compatible forward (`paged_decode_block` -- models/generate's
decode_step/decode_block accept a PagedKVCache and land here).
Host-side page accounting (alloc/free/ownership) is `PagePool`; policy
(who gets pages when) lives in scheduler.py.

THE READ IS BOUNDED BY WHAT EACH SLOT HOLDS (`bounded_read`, PR 29). A
block table is as wide as `max_len`, and a gather of every slot's whole
table costs the table's bytes whatever the slots hold: in the
benchmark's chat cell (8 slots x 2,048 positions of bf16 MHA rows, 22%
live, 3 of 8 slots decoding) that gather and its conversion to f32
were two thirds of the device's busy time. So the K/V read walks a flat
list of (slot, block of pages) items that it builds on the device from
`positions` and `valid`, a few items a step of a `fori_loop` whose trip
count is the list's length: a dead slot costs one block, a live one its
depth rounded up to a block, and because the bound is a value inside
the program the engine still compiles ONE tick and ONE prefill. Each
item leaves its softmax statistics and the items of a slot are folded
as an online softmax folds them; per item the arithmetic is
models/generate.attend_kv's, the read of the contiguous cache. The
step comes from the bytes the table moves (`read_step`): a table that
one step covers (the benchmark's int8 MQA cell: 2 MB a pool a layer) is
gathered whole and read by attend_kv itself, as before -- there a loop
costs more than the rows it skips. Parity of the two forms: a few f32
ulp in f32 and int8, the probabilities' bf16 rounding in bf16
(tests/test_paged_kernel.py on the CPU, chip_smoke.py on the chip).

BOTH LAYOUTS READ THIS WAY (PR 31). The item list, its trip count as a
value and the rule that picks the step are one (`_read_items`,
`read_step`); the step body and the fold are the layout's. K/V heads
(`bounded_read`): attend_kv's products, every item's statistics kept
(2 MB in chat) and folded after the loop. Latent rows
(`bounded_read_latent`): attend_latent's absorbed products over a row
that all 128 heads share, so an item's f32 output (262 KB) outweighs a
small block's rows; the loop carries one running softmax a live slot
and folds a step's items into it, dead slots have no item, and a row
weighs its operations beside its bytes when `read_step` sizes the
block (the benchmark's `dots` tick: 27 pages a block, 7 blocks a
step; its 32-row chunk: 8 pages, 1).

LAYER GROUPS (PR 32). A model whose layers do not all see equally far
back -- sliding-window layers beside global ones -- is served from one
PagedKVCache a GROUP of layers (TransformerLM.cache_groups): the
group's layers' pools, its own block table, its `window`. Every layer
still reads through `bounded_read`; a windowed group's item list
starts at the block that holds the first key its slot's window still
sees (`_read_items`), its mask drops what lies behind
(`generate.causal_mask`), and the host gives the pages wholly behind
back to the group's own pool (pool.WindowGroup), so their table
entries read scratch and are never looked at. A slot with MANY query
rows (`_many_queries`: a 512-row prefill chunk of 28 heads leaves 7.3
MB of f32 output an item) takes the latent read's way through the same
loop: the step sized by the row's operations and the item's statistics
too, one running softmax a slot carried and folded inside the loop. A
model with one group, and every read of a tick or a 32-row chunk, is
the code it was.

SELECTED READS AND A GROUP WITHOUT PAGES (PR 34). Where the model's
softmax layers SELECT the blocks a query reads (transformer.
SparseSelect) a layer's pools hold, beside `k` and `v`, the COMPRESSED
keys `kc`: the mean of `kernel` keys every `stride`, one row a stride
of a page, on the same block table, written by the forward whose row
completes it (`_write_compressed`) and freed with the page. A read
then has three steps: the selection scores the slot's complete
compressed keys and takes the blocks (`select_blocks`: a mask a query
row and K/V head over the table's blocks); a tick's one row a slot
walks, through the SAME loop, the union of its K/V heads' chosen
blocks instead of every block up to its depth (`_read_items`'
`chosen`), each head masked to its own; a chunk's many rows, whose
choices together cover nearly every block, walk every block to the
depth under the same mask. The model's LINEAR layers are a group that
holds no pages at all: `SlotStates`, one (heads, head_dim, head_dim)
f32 array a slot a layer whatever the depth, which a forward that
starts a slot at position 0 starts from zero and which only valid rows
update (models/generate.linear_attend).

THE SELECTION COSTS WHAT IT SELECTS AMONG (PR 35). A tick scores the
compressed keys its decoding slots hold, item by item through the
same list and loop (`_live_scores`: a slot without a valid row has no
item, a live one its complete keys in whole blocks), where it gathered
every slot's whole table of them; a chunk's one slot, and any table
that one step covers, still gathers the table. The k-th best block
score is found by counting over the scores' bits, exactly, where a
sort of every score found it (`_best_blocks`).

What was measured on the v5e (PERF.md section 6, PR 29; one tick's
reads at chat's shapes, 8 layers): whole-table gather 20.6 ms, bounded
2.1 ms; the former Pallas kernel (one page a grid step over the whole
table) 71.6 ms, slower than the gather at every shape of both cells,
so it and the option that chose it are gone (ROADMAP D9). A step of
the loop runs its gathers, converts and products one after the other
at ~310 GB/s of cache bytes; a kernel that overlaps the page fetches
with the products is what is left (ROADMAP S3(b)). The latent read at
the `dots` cell's shapes (six layers, 64 slots, 13.4 k live rows a
layer in 40 of them; PERF.md section 6, PR 31): whole-table gather
7.39 ms, bounded 1.73 ms; a 32-row chunk 128 rows deep 0.90 / 0.15.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..models.generate import (
    _quant_kv,
    attend_kv,
    attend_latent,
    causal_mask,
    latent_query_rows,
    latent_values_up,
    linear_attend,
    token_forward,
)
from ..models.transformer import SparseSelect, TransformerLM
from ..obs.trace import annotate
from ..ops.attention import NEG_INF

# Host-side page accounting lives in pool.py (jax-free — the policy
# layer imports it without pulling this module's device stack);
# re-exported here so device-side callers keep one import surface.
from .pool import PagePool, pages_for  # noqa: F401


@dataclasses.dataclass
class PagedKVCache:
    """Device-side paged cache state of one LAYER GROUP: the page
    pools of its layers + the block table mapping each slot's logical
    positions to physical pages, and the group's `window` (0: its
    layers see every key; else they see the last `window`, and the
    table's entries behind a slot's window may have gone back to
    scratch). A model whose layers all forget alike (every model but
    one with windowed layers beside global ones) has one group and is
    served from one PagedKVCache; one with both kinds from a tuple of
    them, in `TransformerLM.cache_groups()`'s order.
    `page_size` is static metadata (it shapes the compiled program).
    `kernel` names the one read there is and chooses nothing: it stays
    because benchmarks/compile_only.py passes it, and goes with that
    line in a `benchmark` PR (PERF.md section 7)."""

    pages: list[dict]
    block_table: jnp.ndarray      # (slots, pages_per_slot) int32
    page_size: int
    kernel: str = "gather"
    window: int = 0
    # What the forward that produced this cache counted, for the tick
    # record: int32 [expert pairs computed, held experts hit, largest
    # expert load] where the model has expert layers, then the cache
    # rows the read touched, and where the model has windowed layers
    # the rows THEIR reads touched (paged_forward; on the first group's
    # cache); None before any forward.
    counts: jnp.ndarray | None = None

    @property
    def num_pages(self) -> int:
        return next(iter(self.pages[0].values())).shape[0]

    @property
    def slots(self) -> int:
        return self.block_table.shape[0]


jax.tree_util.register_dataclass(
    PagedKVCache, data_fields=["pages", "block_table", "counts"],
    meta_fields=["page_size", "kernel", "window"],
)


@dataclasses.dataclass
class SlotStates:
    """The recurrent layer group's store (TransformerLM.state_layers):
    NO pages and no block table -- `states` holds one (slots, heads,
    head_dim, head_dim) f32 array a linear layer, a slot's row its
    whole memory of the sequence, the same bytes at depth 1 and at
    65,536. `rows` (B,) names, for each batch row of the forward, the
    slot whose state it continues (a tick: every slot in order; a
    prefill chunk: the one slot). A forward whose first valid row sits
    at position 0 starts that slot from zero, whatever the store
    holds: a slot is clean for its next request, and for the same
    request readmitted after a preemption, without anyone clearing it.
    It rides beside the model's PagedKVCache(s) in one tuple."""

    states: list
    rows: jnp.ndarray             # (B,) int32


jax.tree_util.register_dataclass(
    SlotStates, data_fields=["states", "rows"], meta_fields=[])


def init_slot_states(model: TransformerLM, slots: int) -> list:
    """Zero states, one (slots, heads, head_dim, head_dim) f32 array a
    linear layer (SlotStates.states); [] for a model without any."""
    shape = (slots, model.heads, model.head_dim, model.head_dim)
    return [jnp.zeros(shape, jnp.float32) for _ in model.state_layers()]


def init_paged_cache(model: TransformerLM, *, slots: int, num_pages: int,
                     page_size: int, dtype=jnp.float32,
                     max_len: int | None = None,
                     window_pages: int | None = None):
    """Empty page pools + an all-scratch block table: one PagedKVCache,
    or for a model with windowed layers beside global ones a tuple of
    them, one a layer group (TransformerLM.cache_groups), each with the
    pools of ITS layers and a table of its own; `window_pages` sizes a
    windowed group's pools (default: as `num_pages`). A model's linear
    layers have no pools here: their states are `init_slot_states`'.

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
        if jnp.dtype(dtype) == jnp.int8:
            raise ValueError(
                "a latent-attention model's page pool is float32 or "
                "bfloat16 rows; got cache dtype int8")
        pages = [{"c": jnp.zeros(
            (num_pages, page_size, latent_row_lanes(model.attn)), dtype)}
            for _ in range(model.depth)]
        return PagedKVCache(pages=pages, block_table=table,
                            page_size=page_size)
    int8 = jnp.dtype(dtype) == jnp.int8
    sel = model.select
    if sel is not None and (int8 or page_size % sel.stride):
        raise ValueError(
            f"a selecting model's compressed keys are means of float K "
            f"rows, a whole number of them a page; got cache dtype "
            f"{jnp.dtype(dtype)}, page_size {page_size}, stride {sel.stride}")

    def pools(layers: int, num_pages: int) -> list[dict]:
        shape = (num_pages, page_size, model.n_kv, model.head_dim)
        sshape = shape[:-1] + (1,)
        pages = []
        for _ in range(layers):
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
            if sel is not None:     # the compressed keys, a row a stride
                pages[-1]["kc"] = jnp.zeros(
                    (num_pages, page_size // sel.stride) + shape[2:], dtype)
        return pages

    groups = model.cache_groups()
    if not groups:
        raise ValueError("the paged cache holds the K/V of a model's 'attn' "
                         "layers; this model has none")
    if len(groups) == 1:
        return PagedKVCache(pages=pools(len(groups[0][1]), num_pages),
                            block_table=table, page_size=page_size,
                            window=groups[0][0])
    return tuple(
        PagedKVCache(
            pages=pools(len(layers),
                        (window_pages or num_pages) if window else num_pages),
            block_table=table, page_size=page_size, window=window)
        for window, layers in groups)


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
    after it, latent_row_lanes; an invalid token's to scratch page 0),
    writes FIRST, then `bounded_read_latent` reads each slot's pages up
    to its deepest valid position with the block's up-projections
    `wuk`/`wuv`, at the step `read_step` picks from what a row costs
    here: its bytes, the operations of the H*kk query rows that all
    read it, and the f32 statistics a (slot, block) item leaves.
    Returns (o: (B, kk, H*v) f32, new_c, rows the read touched)."""
    b, kk = positions.shape
    pi, of = _write_index(positions, valid, block_table, page_size)
    row = row.astype(c["c"].dtype).reshape(b * kk, -1)
    pool = c["c"].at[pi, of].set(
        jnp.pad(row, ((0, 0), (0, c["c"].shape[-1] - row.shape[-1]))))
    lanes, queries = pool.shape[-1], q.shape[2] * kk
    step = read_step(
        b, block_table.shape[1], page_size, lanes * pool.dtype.itemsize,
        key_flops=2 * queries * (lanes + attn.kv_rank),
        stat_bytes=queries * attn.kv_rank * 4)
    o, rows = bounded_read_latent(
        q, pool, positions, valid, block_table, blk["wuk"], blk["wuv"],
        page_size=page_size, step=step, attn=attn)
    return o, {"c": pool}, rows


def _many_queries(q) -> bool:
    """Whether a (slot, block) item's f32 output -- every head of every
    query row of the slot, (H, kk, hd) -- outweighs a block's rows
    (_BLOCK_BYTES): a 512-row prefill chunk of 28 heads leaves 7.3 MB an
    item. The K/V read then sizes its step as the latent read does
    (the operations of the query rows that share a cache row, and the
    item's statistics, beside the row's bytes) and folds its items into
    one running softmax a slot inside the loop, as bounded_read_latent
    does, instead of keeping every item's for a fold afterwards. A
    tick's one row and a 32-row chunk are far under it (0.5 MB at 32
    heads) and read as PR 29 timed them."""
    _, kk, h, hd = q.shape
    return h * kk * hd * 4 > _BLOCK_BYTES


def _write_compressed(kc, kpool, positions, valid, block_table,
                      page_size: int, sel: SparseSelect):
    """The compressed keys that this forward's rows COMPLETE, into the
    pool `kc` (pages, page_size // stride, Hkv, hd): compressed key j
    is the mean of the keys at positions j * stride .. j * stride +
    kernel - 1, complete when the last of them is written, and lives
    on the page of its FIRST position (so it is freed with it). A
    batch row's valid positions are consecutive (a chunk's rows, a
    tick's one): of kk rows at most ceil(kk / stride) end a kernel;
    their `kernel` keys are read back from `kpool`, this forward's own
    among them, through the block table (they may lie in pages an
    earlier chunk wrote), averaged in f32 and stored in the pool's
    type. A row that ends none writes scratch page 0. Returns the new
    `kc`."""
    b, kk = positions.shape
    stride, kernel = sel.stride, sel.kernel
    ncand = -(-kk // stride)
    hi = jnp.max(jnp.where(valid, positions, -1), axis=1)          # (B,)
    lo = jnp.min(jnp.where(valid, positions, hi[:, None]), axis=1)
    # The c-th position >= lo that ends a stride, and its kernel's rows.
    ends = ((lo // stride + 1) * stride - 1)[:, None] + stride * jnp.arange(
        ncand)[None, :]                                            # (B, C)
    done = (ends <= hi[:, None]) & (ends >= kernel - 1)
    rows = jnp.maximum(ends[:, :, None] - (kernel - 1)
                       + jnp.arange(kernel)[None, None, :], 0)     # (B, C, K)
    page = jnp.take_along_axis(
        block_table, (rows // page_size).reshape(b, -1), axis=1)
    keys = kpool[page.reshape(-1), (rows % page_size).reshape(-1)].reshape(
        b, ncand, kernel, *kpool.shape[2:])
    mean = jnp.mean(keys.astype(jnp.float32), axis=2).astype(kc.dtype)
    first = jnp.maximum(ends - (kernel - 1), 0)        # key j's position
    to = jnp.take_along_axis(block_table, first // page_size, axis=1)
    return kc.at[jnp.where(done, to, 0).reshape(-1),
                 jnp.where(done, first % page_size // stride, 0).reshape(-1)
                 ].set(mean.reshape(b * ncand, *kc.shape[2:]))


# What the selection's scores of one step may take (f32): the query
# heads of a K/V head are scored a few at a time against every
# compressed key of the table, and summed.
_SCORE_BYTES = 64 << 20


def _best_blocks(far, k: int):
    """(..., blocks) bool: the k best-scored of the blocks that are
    candidates (`far` >= 0; the others hold -1), all of them where
    they are fewer. No scatter: every block above the k-th best
    score, and of the blocks that EQUAL it the lowest-numbered that
    fill the rest -- the order of ties `lax.top_k` has.

    The k-th best score is found WITHOUT A SORT, and exactly. A
    candidate's f32 bit pattern, read as an integer, is ordered as its
    value is (a score is >= 0), so with `key` = that integer + 1, and 0
    for a block that is no candidate, the k-th largest key is the
    largest T that at least k keys reach: its 31 bits are settled from
    the top, each by one compare and one count over the blocks. A whole
    sort of 1,024 scores a (row, K/V head) pair, of which one value was
    used, was 2.47 ms a layer of the benchmark's 512-row chunk
    (PERF.md section 6, PR 35). Fewer candidates than k leave T at 0:
    every candidate is above it."""
    key = jnp.where(
        far >= 0,
        (jax.lax.bitcast_convert_type(far, jnp.int32) & 0x7FFFFFFF) + 1, 0)

    def settle(i, kth):
        higher = kth | jnp.left_shift(1, 30 - i)
        return jnp.where(
            jnp.sum(key >= higher[..., None], axis=-1) >= k, higher, kth)

    kth = jax.lax.fori_loop(
        0, 31, settle, jnp.zeros(far.shape[:-1], jnp.int32))[..., None]
    above, ties = key > kth, key == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (far >= 0) & (above | (ties & (jnp.cumsum(ties, axis=-1) <= room)))


def _live_scores(qg, kc, have, valid, block_table, step: tuple[int, int],
                 scale):
    """A tick's scores of the compressed keys that its slots HOLD: qg
    (B, 1, Hkv, g, hd) against each slot's first `have` (B, 1)
    compressed keys, nothing of a slot without a valid row and nothing
    past a slot's depth. The items are the K/V read's (`_read_items`
    over the pool `kc`, whose page holds `kc.shape[1]` keys; `step` =
    (pages a block, blocks a step), `_index_step`'s): a loop
    whose trip count is a value gathers a step's items' pages of `kc`,
    scores them against their slot's query heads and leaves the scaled
    scores at their place in one f32 buffer. An item past the list's
    end repeats the last one: the same scores to the same place.
    Returns (scores (B, Hkv, g, 1, compressed keys of the table),
    NEG_INF where no item wrote; rows gathered: the items' -- the last
    step's repeats are not counted -- int32)."""
    b, _, hkv, g, hd = qg.shape
    cpp = kc.shape[1]                             # compressed keys a page
    ncomp = block_table.shape[1] * cpp
    per_block, per_step = step
    nblk = -(-block_table.shape[1] // per_block)
    width = per_block * cpp                       # compressed keys a block
    _, ends, steps, slot, _, item_pages, first_key = _read_items(
        have - 1, valid & (have > 0), block_table, cpp, step, dead_blocks=0)

    def take(i, buf):
        at = jnp.minimum(i * per_step + jnp.arange(per_step), ends[-1] - 1)
        sl, key0 = slot[at], first_key[at]
        rows = kc[item_pages[at].reshape(-1)].reshape(
            per_step, width, hkv, hd)
        s = jnp.einsum("ihgd,ijhd->ihgj", qg[sl, 0], rows,
                       preferred_element_type=jnp.float32) * scale
        for n in range(per_step):
            buf = jax.lax.dynamic_update_slice(
                buf, s[n][None, :, :, None, :], (sl[n], 0, 0, 0, key0[n]))
        return buf

    buf = jax.lax.fori_loop(
        0, steps, take,
        jnp.full((b, hkv, g, 1, nblk * width), NEG_INF, jnp.float32))
    return buf[..., :ncomp], (ends[-1] * width).astype(jnp.int32)


# What a page gathered alone weighs at least, in bytes' time: XLA's
# gather moves a 512 B page of compressed keys in the ~12 ns that 4 KB
# of a K/V page take (40 GB/s against the bounded read's 310: PERF.md
# section 6, PR 35).
_GATHER_BYTES = 4 << 10


def _index_step(slots: int, npages: int, kc) -> tuple[int, int]:
    """read_step for the compressed pool `kc` (pages, keys a page, Hkv,
    hd): a key weighs its bytes, or its share of what its page weighs
    gathered alone. The benchmark's tick (32 slots x 4,096 pages of one
    512 B key): blocks of 256 keys, 8 a step."""
    cpp = kc.shape[1]
    row = int(np.prod(kc.shape[2:])) * kc.dtype.itemsize
    return read_step(slots, npages, cpp, max(row, _GATHER_BYTES // cpp))


def select_blocks(q, kc, positions, valid, block_table, page_size: int,
                  sel: SparseSelect):
    """The blocks each query row reads, a K/V head (SparseSelect,
    InfLLM-V2): q (B, kk, H, hd) against the slot's compressed keys in
    the pool `kc` (a row a stride, 1/16 of the K rows' bytes). For the
    query at position t and K/V head g:
    p_h = softmax over the compressed keys complete at t of q_h . kc_j
    / sqrt(hd), for each of g's query heads; P_j their sum; a block's
    score the largest P_j over the compressed keys that overlap it;
    chosen = the first `init_blocks` blocks, the blocks overlapping
    keys t - window + 1 .. t, and the `topk` best-scored of the other
    blocks up to t's own (ties to the lower block: _best_blocks);
    every block up to t's own while t + 1 < dense_len.

    What is scored follows what is LIVE where the table is worth it:
    one query row a slot (a tick) over a table that `read_step` gives
    a loop scores, item by item, the compressed keys that slots with a
    valid row hold (`_live_scores`) -- the gather of every slot's whole
    table, 32 x 4,096 rows of 512 B where 7 slots decode 600-900 keys
    deep, was 2.31 ms a layer of the benchmark's tick (PERF.md section
    6, PR 35). Many rows of one slot (a chunk), and every table that
    one step covers, gather the table through the block table and
    score it whole. The softmax, the sums and the maxima that follow
    are one code over either's scores.
    Returns (chosen (B, Hkv, kk, blocks of the table) bool, compressed
    keys the valid rows scored, rows of `kc` the gathers moved, blocks
    the valid rows chose -- three int32, K/V heads summed but for the
    gathered rows, which they share)."""
    b, kk, h, hd = q.shape
    hkv = kc.shape[2]
    g = h // hkv
    npages, cpp = block_table.shape[1], kc.shape[1]
    ncomp = npages * cpp
    r, lead = sel.block // sel.stride, sel.kernel // sel.stride - 1
    nb = -(-npages * page_size // sel.block)
    have = sel.compressed(positions + 1)                       # (B, kk)
    there = (jnp.arange(ncomp)[None, None, :] < have[:, :, None])[:, None]
    qg = q.reshape(b, kk, hkv, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    step = _index_step(b, npages, kc)
    if kk == 1 and step[1] < b * -(-npages // step[0]):
        few = g
        raw, gathered = _live_scores(qg, kc, have, valid, block_table, step,
                                     scale)
    else:
        few = max(1, min(g, _SCORE_BYTES // (4 * b * hkv * kk * ncomp)))
        comp = kc[block_table].reshape(b, ncomp, hkv, hd)
        raw, gathered = None, jnp.int32(b * ncomp)
    total = jnp.zeros((b, hkv, kk, ncomp), jnp.float32)
    for g0 in range(0, g, few):
        s = raw if raw is not None else jnp.einsum(
            "bqhgd,bjhd->bhgqj", qg[:, :, :, g0:g0 + few], comp,
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(there[:, :, None], s, NEG_INF)
        total = total + jnp.sum(
            jnp.where(there[:, :, None], jax.nn.softmax(s, axis=-1), 0.0),
            axis=2)
    # Compressed key j overlaps block j // r and, its kernel reaching
    # `lead` strides further, the block after: pad `lead` in front and
    # a block's score is the largest of its own r entries and the next
    # block's first `lead`.
    padded = jnp.pad(total, ((0, 0),) * 3 + ((lead, (nb + 1) * r - ncomp
                                              - lead),))
    score = jnp.max(padded[..., : nb * r].reshape(b, hkv, kk, nb, r), axis=-1)
    if lead:
        score = jnp.maximum(score, jnp.max(
            padded[..., r:].reshape(b, hkv, kk, nb, r)[..., :lead], axis=-1))
    t = positions[:, None, :, None]                            # (B, 1, kk, 1)
    blk = jnp.arange(nb)[None, None, None, :]
    upto = blk <= t // sel.block
    near = (blk < sel.init_blocks) | (
        blk >= jnp.maximum(t - sel.window + 1, 0) // sel.block)
    far = jnp.where(upto & ~near, score, -1.0)      # a score is >= 0
    picked = _best_blocks(far, min(sel.topk, nb))
    chosen = upto & jnp.where(t + 1 < sel.dense_len, True, near | picked)
    live = valid[:, None, :]
    return (chosen,
            (hkv * jnp.sum(jnp.where(valid, have, 0))).astype(jnp.int32),
            gathered,
            jnp.sum(jnp.where(live[..., None], chosen, False)).astype(
                jnp.int32))


def chosen_walk(chosen, sel: SparseSelect, step: tuple[int, int],
                page_size: int):
    """The items a tick walks in place of every block to its depth:
    `chosen` (B, Hkv, 1, blocks) -> ((need (B,), blocks (B, most)), the
    walk's step). A slot's items are the UNION of its K/V heads' chosen
    blocks, ascending, a selection block each (each head is masked to
    its own in the read); `most` is what a slot can choose at most:
    every block under dense_len, or the near ones and each head's
    topk. A step takes as many of them as move what `step`, read_step's
    for whole blocks of the table, moved."""
    union = jnp.any(chosen[:, :, 0], axis=1)                   # (B, nb)
    most = min(union.shape[1], max(
        -(-sel.dense_len // sel.block),
        sel.init_blocks + -(-sel.window // sel.block) + 1
        + chosen.shape[1] * sel.topk))
    walk = (jnp.sum(union, axis=1).astype(jnp.int32),
            jnp.argsort(~union, axis=1, stable=True)[:, :most].astype(
                jnp.int32))
    return walk, (sel.block // page_size,
                  max(1, step[0] * step[1] * page_size // sel.block))


def paged_update_attend(c: dict, q, k, v, positions, valid, block_table,
                        page_size: int, window: int = 0,
                        select: SparseSelect | None = None):
    """One layer's paged write + attention read.

    q: (B, kk, H, hd); k/v: (B, kk, Hkv, hd); positions: (B, kk)
    absolute positions; valid: (B, kk) bool -- invalid tokens (padding
    beyond a prompt's length, dead slots) write to scratch page 0 at
    offset 0 instead, so they can never touch a page owned by a live
    sequence. Writes land FIRST (in-chunk causality: row i then reads
    rows <= i through the read), then `bounded_read` reads each slot's
    pages up to its deepest valid position, masked to key positions <=
    the row's own; rows of a page past a slot's written extent hold
    whatever they hold -- the mask keeps them out of the softmax, and
    pages past the slot's last block are not touched at all. Under a
    sliding `window` a row also sees no key at or before its position
    - window, and the read starts at the block that holds the smallest
    valid position's window: blocks wholly behind it are not touched
    either (their table entries may be scratch).

    Under a block selection `select` (the pools then hold `kc` too):
    the compressed keys these rows complete are written after the
    keys, `select_blocks` chooses each row's blocks a K/V head, and
    the read sees a key only in a chosen block. One row a slot (a
    tick) walks the union of its K/V heads' chosen blocks, one item a
    selection block; more rows walk every block to the depth, masked.
    Returns (o: (B, kk, H*hd) f32, new_c, cache rows the read touched)
    and, under a selection, select_blocks' three counts.
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
    key_bytes = sum(int(np.prod(a.shape[2:])) * a.dtype.itemsize
                    for a in new_c.values())
    # Many query rows a slot: a cache row costs its score and its value
    # product for every one of them (4 operations a query element), and
    # an item leaves that many f32 values -- folded into a carry a
    # slot of that size.
    many = 4 * q.shape[2] * kk * hd
    step = read_step(b, block_table.shape[1], page_size, key_bytes,
                     **(dict(key_flops=many, stat_bytes=many,
                             carry_bytes=many)
                        if _many_queries(q) else {}))
    if select is None:
        o, rows = bounded_read(
            q, new_c, positions, valid, block_table, page_size=page_size,
            step=step, window=window)
        return o, new_c, rows
    with annotate("attn.sparse_select"):
        kc = _write_compressed(c["kc"], new_c["k"], positions, valid,
                               block_table, page_size, select)
        chosen, *counted = select_blocks(
            q, kc, positions, valid, block_table, page_size, select)
    npages = block_table.shape[1]
    walk = None
    if (kk == 1 and select.block % page_size == 0
            and step[1] < b * -(-npages // step[0])):
        # One row a slot and a table worth a loop.
        walk, step = chosen_walk(chosen, select, step, page_size)
    with annotate("attn.sparse_read"):
        o, rows = bounded_read(
            q, new_c, positions, valid, block_table, chosen, walk,
            page_size=page_size, step=step, sel_block=select.block)
    return o, {**new_c, "kc": kc}, rows, counted


# What one (slot, block) item of the bounded read moves at least, and
# what one step of its loop moves at least, K and V together. A step of
# the loop costs some microseconds whatever it moves (a gather, two
# products, the carry's update: PERF.md section 6, PR 29), so it has to
# move megabytes to cover them; a block is what a slot's read is
# rounded up to, so it is as small as a lane tile of keys allows.
_BLOCK_BYTES = 1 << 20
_STEP_BYTES = 8 << 20
# Operations that take the chip as long as one byte from its memory
# (197 TFLOP/s over 819 GB/s on the v5e): a cache row that many query
# rows read weighs its operations too, in bytes' time.
_FLOPS_PER_BYTE = 240


def read_step(slots: int, npages: int, page_size: int, key_bytes: int,
              *, key_flops: int = 0, stat_bytes: int = 0,
              carry_bytes: int = 0) -> tuple[int, int]:
    """(pages a block, blocks a step) of the bounded read, from what
    the table moves: `key_bytes` is one cache row, K and V and their
    scales. A block is at least a lane tile of keys and _BLOCK_BYTES; a
    step is as many blocks as make _STEP_BYTES, at most every block of
    every slot -- a table so small is read whole in one step.

    Where a row is shared by many query rows (the latent layout: every
    head reads the one row, 128 heads x 1 or 32 queries) its operations
    weigh as much as its bytes or more, and each (slot, block) item
    leaves f32 statistics larger than a small block's rows: `key_flops`
    (operations one cache row costs) counts at _FLOPS_PER_BYTE beside
    the row's bytes, and `stat_bytes` (what an item leaves) beside a
    block's in the step. The K/V layouts pass neither: there both are
    a small part of the bytes (a chat tick: 0.4% and 0.8%), and their
    steps are as PR 29 timed them.

    Where the loop carries ONE running softmax a slot and every item
    is folded into it (the K/V read of a slot with many query rows,
    bounded_read's running fold), each item reads and writes its slot's
    `carry_bytes` whatever its block holds, so a block weighs at least
    that, in whole lane tiles of keys: a 512-row chunk of 28 heads
    (7.3 MB of carry, 32.6 KB a key) takes 512-key blocks, and its
    chunk 25.5 ms where 128-key blocks took 31.6 (PERF.md section 6,
    PR 32)."""
    weight = key_bytes + key_flops // _FLOPS_PER_BYTE
    keys = max(_LANES, -(-_BLOCK_BYTES // weight))
    if carry_bytes:
        keys = max(keys, -(-2 * carry_bytes // (weight * _LANES)) * _LANES)
    per_block = min(npages, -(-keys // page_size))
    blocks = slots * -(-npages // per_block)
    per_step = -(-_STEP_BYTES // (per_block * page_size * weight + stat_bytes))
    return per_block, min(blocks, per_step)


def _read_items(positions, valid, block_table, page_size: int,
                step: tuple[int, int], dead_blocks: int, window: int = 0,
                walk=None):
    """The flat list of (slot, block of pages) items both bounded reads
    walk, built on the device from `positions` and `valid`: a live
    slot's blocks up to its deepest valid position -- from block 0, or
    under a sliding `window` from the block that holds the first key
    its SMALLEST valid position still sees (position - window + 1) --
    a dead slot's (no
    valid token) `dead_blocks` -- 1 for the K/V read, whose dead rows
    read one block of scratch, 0 for the latent read, which skips them.
    Returns (need, ends, steps, slot, live, item_pages, first_key):
    blocks a slot needs (B,), their running total, the loop's trip
    count (a value), and per item its slot, whether the list holds it,
    its pages (scratch where it does not) and its first key's
    position. The list is padded to whole steps.

    `walk` = (need (B,), blocks (B, most)) names each slot's blocks
    instead (a block selection's, paged_update_attend): a slot's items
    are the first need[slot] entries of its row, whatever their
    numbers, and no depth is looked at."""
    b, npages = block_table.shape
    per_block, per_step = step
    nblk = -(-npages // per_block)
    width = per_block * page_size                 # keys a block
    # Each slot's blocks; its table, padded with scratch to whole blocks.
    if walk is None:
        most = nblk
        depth = jnp.max(jnp.where(valid, positions, 0), axis=1)
        need = jnp.minimum(depth // width + 1, nblk)              # (B,)
        if window:
            lowest = jnp.min(jnp.where(valid, positions, depth[:, None]),
                             axis=1)
            first = jnp.maximum(lowest - window + 1, 0) // width  # (B,)
            need = need - first
        if not dead_blocks:
            need = jnp.where(jnp.any(valid, axis=1), need, 0)
    else:
        need, named = walk
        most = named.shape[1]
    ends = jnp.cumsum(need)
    steps = -(-ends[-1] // per_step)
    blocks = jnp.pad(block_table, ((0, 0), (0, nblk * per_block - npages))
                     ).reshape(b * nblk, per_block)
    # The flat list: item i is block i - (ends - need)[slot] of the
    # slot whose run of items holds i (under a walk: that entry of its
    # row). Past the list's end there is no item: what the last step
    # computes there (scratch pages) is never folded.
    item = jnp.arange(-(-b * most // per_step) * per_step)
    slot = jnp.minimum(jnp.sum(item[:, None] >= ends[None, :], axis=1), b - 1)
    blk = item - (ends - need)[slot]
    if window:
        blk = blk + first[slot]
    live = item < ends[-1]
    if walk is not None:
        blk = named[slot, jnp.where(live, blk, 0)]
    item_pages = jnp.where(live[:, None],
                           blocks[slot * nblk + jnp.where(live, blk, 0)], 0)
    return need, ends, steps, slot, live, item_pages, blk * width


@functools.partial(jax.jit, static_argnames=("page_size", "step", "window",
                                             "sel_block"))
def bounded_read(q, c: dict, positions, valid, block_table, chosen=None,
                 walk=None, *, page_size: int, step: tuple[int, int],
                 window: int = 0, sel_block: int = 0):
    """The attention read over one layer's page pools `c`, bounded by
    what each slot holds; `step` = (per_block, per_step) is read_step's.
    Jitted, so a program of many layers traces it once and not once a
    layer (set-up time: 0.7 s of chat's two programs otherwise).

    A slot's keys are read in BLOCKS of `per_block` pages, and only the
    blocks up to its deepest valid position (a dead row, position 0:
    one block). The (slot, block) items of all slots form one flat
    list, built from `positions` and `valid` (`_read_items`, shared
    with the latent layout's bounded_read_latent; what follows -- the
    step body and the fold -- is this layout's); a `fori_loop` takes
    `per_step` items a step -- gathers their pages, scores them against
    their slot's queries, and leaves each item's softmax statistics
    (row maximum, denominator, unnormalised output) in a buffer -- and
    its trip count is the list's length over `per_step`: a value inside
    the program, so one compiled program serves every depth. The items
    of a slot are then folded as an online softmax folds them.
    The arithmetic per item is attend_kv's: scores of `q` against keys
    in the cache's type, f32 statistics, probabilities in the values'
    type for the second product, int8 scales outside the products; only
    the order of the softmax's sums differs.

    Where one step covers every block of every slot (a small table),
    the loop would run once over the whole table, and the read IS the
    gather of the table and attend_kv.

    Under a sliding `window` the mask also drops keys at or before a
    row's position - window, and a slot's items start at its window's
    first block (_read_items). Where a slot has many query rows
    (_many_queries: a 512-row chunk) an item's statistics are not kept:
    the loop carries one running (maximum, denominator, output) a slot
    and folds each of a step's items into its slot's, as
    bounded_read_latent does.

    Under a block selection `chosen` (B, Hkv, kk, blocks of `sel_block`
    keys; select_blocks) a K/V head's queries see a key only where its
    block is chosen: one more term of the mask, in every form. `walk`
    (need, blocks) then names the items of a slot's ONE row, a
    selection block each (_read_items), in place of every block up to
    its depth.

    Returns (o: (B, kk, H*hd) f32, cache rows the read touched: steps
    taken x rows a step, int32)."""
    b, kk, h, hd = q.shape
    hkv = c["k"].shape[2]
    g = h // hkv
    npages = block_table.shape[1]
    int8 = c["k"].dtype == jnp.int8
    per_block, per_step = step
    nblk = -(-npages // per_block)

    if per_step >= b * nblk and walk is None:
        length = npages * page_size
        rows = {n: c[n][block_table].reshape(b, length, *c[n].shape[2:])
                for n in c}
        mask = causal_mask(jnp.arange(length)[None, None, :],
                           positions[:, :, None], window)
        if chosen is not None:      # every block's verdict, a key each
            mask = mask[:, None] & jnp.repeat(
                chosen, sel_block, axis=-1)[..., :length]
        o = attend_kv(q, rows["k"], rows["v"], mask,
                      cks=rows.get("ks"), cvs=rows.get("vs"))
        return o, jnp.int32(b * length)

    width = per_block * page_size                 # keys a block
    if chosen is not None:
        # An item's keys are one run of whole selection blocks (or lie
        # in one): its verdicts are a SLICE of its slot's, blocks
        # first so that the slice is one piece -- a gather a key cost
        # 5 ms an item on the chip (PERF.md section 6, PR 34).
        if width % sel_block and sel_block % width:
            raise ValueError(f"items of {width} keys and selection blocks "
                             f"of {sel_block}: want whole ones of the other")
        by_block = jnp.transpose(chosen, (0, 3, 1, 2))    # (B, nb, Hkv, kk)
        nsel = max(1, width // sel_block)
    need, ends, steps, slot, live, item_pages, first_key = _read_items(
        positions, valid, block_table, page_size, step, dead_blocks=1,
        window=window, walk=walk)
    if walk is not None:
        nblk = walk[1].shape[1]     # a slot's items at most
    qg = q.reshape(b, kk, hkv, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    running = _many_queries(q)

    def items(at):
        """The `per_step` items from `at`: their slots, row maxima,
        unnormalised probabilities and outputs."""
        sl = jax.lax.dynamic_slice_in_dim(slot, at, per_step)
        pages = jax.lax.dynamic_slice_in_dim(item_pages, at, per_step)
        key0 = jax.lax.dynamic_slice_in_dim(first_key, at, per_step)
        rows = {n: c[n][pages.reshape(-1)].reshape(
            per_step, width, *c[n].shape[2:]) for n in c}
        logits = jnp.einsum(
            "iqhgd,ikhd->ihgqk", qg[sl],
            rows["k"].astype(jnp.float32) if int8 else rows["k"],
            preferred_element_type=jnp.float32) * scale
        if int8:
            logits = logits * jnp.transpose(
                rows["ks"], (0, 2, 3, 1))[:, :, None, :, :]
        mask = causal_mask(
            key0[:, None, None] + jnp.arange(width)[None, None, :],
            positions[sl][:, :, None], window)[:, None, None, :, :]
        if chosen is not None:
            picks = by_block[sl[:, None], (key0 // sel_block)[:, None]
                             + jnp.arange(nsel)[None, :]]  # (P, nsel, Hkv, kk)
            mask = mask & jnp.repeat(
                jnp.transpose(picks, (0, 2, 3, 1)), width // nsel,
                axis=-1)[:, :, None]
        if running:     # an item past the list's end folds as nothing
            mask = mask & jax.lax.dynamic_slice_in_dim(
                live, at, per_step)[:, None, None, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
        m = jnp.max(logits, axis=-1)
        p = jnp.where(mask, jnp.exp(logits - m[..., None]), 0.0)
        if int8:
            pv = p * jnp.transpose(rows["vs"], (0, 2, 3, 1))[:, :, None, :, :]
            o = jnp.einsum("ihgqk,ikhd->ihgqd", pv,
                           rows["v"].astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        else:
            o = jnp.einsum("ihgqk,ikhd->ihgqd", p.astype(rows["v"].dtype),
                           rows["v"], preferred_element_type=jnp.float32)
        return sl, m, p, o

    if running:
        def fold(i, carry):
            sl, m, p, o = items(i * per_step)
            denom = jnp.sum(p, axis=-1)
            for j in range(per_step):       # one slot's row of the carry
                old = [jax.lax.dynamic_index_in_dim(x, sl[j], keepdims=False)
                       for x in carry]
                top = jnp.maximum(old[0], m[j])
                keep, w = jnp.exp(old[0] - top), jnp.exp(m[j] - top)
                new = (top, keep * old[1] + w * denom[j],
                       keep[..., None] * old[2] + w[..., None] * o[j])
                carry = tuple(
                    jax.lax.dynamic_update_index_in_dim(x, n, sl[j], 0)
                    for x, n in zip(carry, new))
            return carry

        stat = (b, hkv, g, kk)
        _, denom, o = jax.lax.fori_loop(
            0, steps, fold,
            (jnp.full(stat, NEG_INF, jnp.float32),
             jnp.zeros(stat, jnp.float32),
             jnp.zeros(stat + (hd,), jnp.float32)))
        o = o / jnp.where(denom > 0, denom, 1.0)[..., None]
        o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(b, kk, h * hd)
        return o, (steps * (per_step * width)).astype(jnp.int32)

    stat = (slot.shape[0], hkv, g, kk)

    def take(i, carry):
        m_buf, l_buf, o_buf = carry
        at = i * per_step
        _, m, p, o = items(at)
        put = jax.lax.dynamic_update_slice_in_dim
        return (put(m_buf, m, at, 0), put(l_buf, jnp.sum(p, axis=-1), at, 0),
                put(o_buf, o, at, 0))

    m_buf, l_buf, o_buf = jax.lax.fori_loop(
        0, steps, take,
        (jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32),
         jnp.zeros(stat + (hd,), jnp.float32)))
    # Fold each slot's items: block j of slot s is item (ends - need)[s]
    # + j while j < need[s]; a block not read weighs nothing.
    j = jnp.arange(nblk)[None, :]
    mine = jnp.where(j < need[:, None], (ends - need)[:, None] + j, 0)
    read = (j < need[:, None])[:, :, None, None, None]
    m = jnp.where(read, m_buf[mine], NEG_INF)        # (B, nblk, Hkv, g, kk)
    top = jnp.max(m, axis=1, keepdims=True)
    w = jnp.where(read, jnp.exp(m - top), 0.0)
    denom = jnp.sum(w * l_buf[mine], axis=1)
    if window:      # a row that is no request's may see no key at all
        denom = jnp.where(denom > 0, denom, 1.0)
    o = jnp.sum(w[..., None] * o_buf[mine], axis=1) / denom[..., None]
    o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(b, kk, h * hd)
    return o, (steps * (per_step * width)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("page_size", "step", "attn"))
def bounded_read_latent(q, pool, positions, valid, block_table, wuk, wuv, *,
                        page_size: int, step: tuple[int, int], attn):
    """bounded_read for the latent layout: one pool of rows
    (pages, page_size, stored lanes), every head reading the same row.
    The item list and its trip count are bounded_read's (_read_items;
    a dead slot has no item here); the step body is attend_latent's
    absorbed products, and the fold runs INSIDE the loop.

    Once per query, outside the loop: the (H*kk, lanes) query rows
    q~_h = wuk_h q_n,h (latent_query_rows). Per item: scores of its
    slot's query rows against the block's rows as they lie (f32
    accumulation), f32 statistics, probabilities exp(s - block max) in
    the rows' type for the product with the rows' first kv_rank lanes.
    An item's unnormalised output is (H*kk, kv_rank) f32 -- 262 KB at
    128 heads, more than a 128-row block's rows -- so the items are
    not kept for a fold afterwards (bounded_read's buffers would be
    268 MB a layer at the benchmark's tick): the loop carries ONE
    running (maximum, denominator, output) a live slot, 17 MB at 64
    slots, and merges a step's items into the window of slots they
    belong to as an online softmax does. Live slots are numbered in
    order (their RANK), so the slots of a step's consecutive items are
    consecutive ranks and the window is one dynamic slice; items of one
    slot in one step are summed by select-and-add, which is exact and
    leaves the carry's layout slot-major (a one-hot product made XLA
    lay the carry out head-major, and every window update then cost
    15 us at an unaligned sublane offset: PERF.md section 6, PR 31).
    After the loop: normalise, `wuv` once per query
    (latent_values_up), and each slot takes its rank's row (a dead
    slot zeros: its output is nobody's). Only the order of the
    softmax's sums differs from attend_latent over the whole table.

    Where one step covers every block of every slot (a small table)
    the read IS the gather of the table and attend_latent.

    Returns (o: (B, kk, H*v) f32, cache rows the read touched: steps
    taken x rows a step, int32)."""
    b, kk, h, _ = q.shape
    npages = block_table.shape[1]
    per_block, per_step = step
    nblk = -(-npages // per_block)
    if per_step >= b * nblk:
        length = npages * page_size
        rows = pool[block_table].reshape(b, length, -1)
        mask = jnp.arange(length)[None, None, :] <= positions[:, :, None]
        return (attend_latent(q, rows, mask, wuk, wuv, attn),
                jnp.int32(b * length))

    width = per_block * page_size                 # rows a block
    need, _, steps, slot, live, item_pages, first_key = _read_items(
        positions, valid, block_table, page_size, step, dead_blocks=0)
    rank = jnp.cumsum(need > 0) - 1               # (B,), a live slot's
    dest = rank[slot]
    qrow = latent_query_rows(q, wuk, attn, pool)              # (B, H*kk, C)
    win = min(per_step, b)                        # slots a step can touch

    def take(i, carry):
        sl, pages, key0, here, to = (
            jax.lax.dynamic_slice_in_dim(x, i * per_step, per_step)
            for x in (slot, item_pages, first_key, live, dest))
        rows = pool[pages.reshape(-1)].reshape(per_step, width, -1)
        logits = jnp.einsum("imc,ikc->imk", qrow[sl], rows,
                            preferred_element_type=jnp.float32).reshape(
            per_step, h, kk, width)
        mask = (((key0[:, None, None] + jnp.arange(width)[None, None, :])
                 <= positions[sl][:, :, None])
                & here[:, None, None])[:, None, :, :]
        logits = jnp.where(mask, logits * attn.softmax_scale, NEG_INF)
        m = jnp.max(logits, axis=-1)                          # (P, H, kk)
        p = jnp.where(mask, jnp.exp(logits - m[..., None]), 0.0)
        o = jnp.einsum("imk,ikr->imr",
                       p.astype(rows.dtype).reshape(per_step, h * kk, width),
                       rows[..., :attn.kv_rank],
                       preferred_element_type=jnp.float32)
        m = m.reshape(per_step, h * kk)
        denom = jnp.sum(p, axis=-1).reshape(per_step, h * kk)
        # Merge into the window of live slots this step's items hold.
        first = jnp.minimum(to[0], b - win)
        at_slot = jnp.clip(to - first, 0, win - 1)            # (P,)
        mine = at_slot[:, None] == jnp.arange(win)[None, :]   # (P, win)
        old = [jax.lax.dynamic_slice_in_dim(x, first, win) for x in carry]
        top = jnp.maximum(old[0], jnp.max(
            jnp.where(mine[:, :, None], m[:, None, :], NEG_INF), axis=0))
        keep = jnp.exp(old[0] - top)                          # (win, H*kk)
        w = jnp.exp(m - top[at_slot])                         # (P, H*kk)

        def add(x):     # sum of a slot's items: exact, and no layout's
            sel = mine.reshape(mine.shape + (1,) * (x.ndim - 1))
            return jnp.sum(jnp.where(sel, x[:, None], 0.0), axis=0)

        new = (top, keep * old[1] + add(w * denom),
               keep[..., None] * old[2] + add(w[..., None] * o))
        return tuple(jax.lax.dynamic_update_slice_in_dim(x, n, first, 0)
                     for x, n in zip(carry, new))

    stat = (b, h * kk)
    _, denom, o = jax.lax.fori_loop(
        0, steps, take,
        (jnp.full(stat, NEG_INF, jnp.float32), jnp.zeros(stat, jnp.float32),
         jnp.zeros(stat + (attn.kv_rank,), jnp.float32)))
    ot = o / jnp.where(denom > 0, denom, 1.0)[..., None]
    out = latent_values_up(ot.reshape(b, h, kk, attn.kv_rank), wuv)
    out = jnp.where((need > 0)[:, None, None], out[rank], 0.0)
    return out, (steps * (per_step * width)).astype(jnp.int32)


def _carried_state(states, first):
    """What a forward continues: each batch row's slot's state
    (B, heads, head_dim, head_dim), or zeros where the row's first
    valid position `first` (B,) is 0 -- a request's first chunk, on
    first admission or after a preemption, whoever had the slot."""
    return jnp.where((first == 0)[:, None, None, None], 0.0, states)


def paged_forward(model: TransformerLM, params, toks, positions, valid,
                  cache):
    """toks (B, kk) through the model against the paged cache — the
    paged twin of decode_block's contiguous path, same token_forward
    skeleton, attend swapped (by the pool's layout: K/V heads, or
    latent rows where the model has latent attention). `cache` is one
    PagedKVCache, or one a layer group for a model with windowed
    layers beside global ones (TransformerLM.cache_groups' order):
    each layer writes and reads its group's pools through its group's
    table, a windowed group's under its window.
    positions/valid: (B, kk).
    Returns (logits (B, kk, vocab) f32, the new cache in the form it
    came); the new cache (of several: the first) carries this
    forward's `counts`: the expert layers' three
    where the model has any (and zeros for a latent model without),
    then the cache rows the read touched, all layers together, then
    where the model has windowed layers the rows their reads touched,
    then where it selects blocks or has linear layers four more: the
    compressed keys the valid rows scored, the compressed keys the
    selection's gathers moved, the blocks the valid rows chose (layers
    summed; K/V heads too, but for the gathers they share), and the
    states written (slots with a valid row x linear layers). A model
    with linear layers brings their `SlotStates` as one more element
    of the tuple, after its PagedKVCache(s), and gets it back there."""
    every = cache if isinstance(cache, tuple) else (cache,)
    caches = tuple(c for c in every if not isinstance(c, SlotStates))
    store = next((c for c in every if isinstance(c, SlotStates)), None)
    groups = model.cache_groups()
    if [c.window for c in caches] != [w for w, _ in groups]:
        raise ValueError(
            f"the model's layer groups have windows {[w for w, _ in groups]}"
            f"; the cache's {[c.window for c in caches]}")
    if len(model.state_layers()) != (
            0 if store is None else len(store.states)):
        raise ValueError(
            f"the model's linear layers {model.state_layers()} want one "
            "state each in a SlotStates beside the paged cache")
    group_of = {i: g for g, (_, layers) in enumerate(groups) for i in layers}
    new_pages: list[list[dict]] = [[] for _ in caches]
    new_states: list = []
    rows_read = rows_window = updated = 0
    selected = [0, 0, 0]    # compressed keys scored, gathered; blocks chosen

    def attend(i, q, k, v):
        nonlocal rows_read, rows_window, selected, updated
        if model.mixer(i) == "linear":
            # This row's slot's state; from zero where its first valid
            # row sits at position 0 (a slot's new request, or the same
            # one readmitted). Only slots with a valid row are written.
            with annotate("attn.linear_state"):
                states = store.states[len(new_states)]
                any_valid = jnp.any(valid, axis=1)
                first = jnp.min(jnp.where(valid, positions,
                                          jnp.iinfo(jnp.int32).max), axis=1)
                old = _carried_state(states[store.rows], first)
                o, new = linear_attend(q, k, v, old, valid,
                                       model.linear.log_decay(model.heads))
                to = jnp.where(any_valid, store.rows, states.shape[0])
                new_states.append(states.at[to].set(new, mode="drop"))
                updated += jnp.sum(any_valid)
            return o
        g = group_of[i]
        c, pools = caches[g], caches[g].pages[len(new_pages[g])]
        if model.select is not None:
            o, new_c, n, counted = paged_update_attend(
                pools, q, k, v, positions, valid, c.block_table,
                c.page_size, select=model.select)
            selected = [a + b for a, b in zip(selected, counted)]
        elif model.attn is not None:
            o, new_c, n = paged_update_attend_latent(
                pools, q, k, positions, valid, c.block_table,
                c.page_size, params["blocks"][i], model.attn)
        elif len(caches) == 1 and not c.window:
            o, new_c, n = paged_update_attend(
                pools, q, k, v, positions, valid, c.block_table,
                c.page_size)
        else:
            with annotate("attn.window_read" if c.window
                          else "attn.global_read"):
                o, new_c, n = paged_update_attend(
                    pools, q, k, v, positions, valid, c.block_table,
                    c.page_size, c.window)
        rows_read += n
        if c.window:
            rows_window += n
        new_pages[g].append(new_c)
        return o

    logits, counts = token_forward(model, params, toks, positions, attend,
                                   valid)
    rows_read = jnp.reshape(jnp.asarray(rows_read, jnp.int32), (1,))
    if model.attn is not None and counts is None:
        counts = jnp.zeros((3,), jnp.int32)
    counts = rows_read if counts is None else jnp.concatenate(
        [counts, rows_read])
    if model.window:
        counts = jnp.concatenate(
            [counts, jnp.reshape(jnp.asarray(rows_window, jnp.int32), (1,))])
    if model.select is not None or store is not None:
        counts = jnp.concatenate([counts, jnp.stack([
            jnp.asarray(n, jnp.int32) for n in (*selected, updated)])])
    new = tuple(
        dataclasses.replace(c, pages=p, counts=None if g else counts)
        for g, (c, p) in enumerate(zip(caches, new_pages)))
    if store is not None:
        new = new + (dataclasses.replace(store, states=new_states),)
    return logits, new if isinstance(cache, tuple) else new[0]


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
    first = cache[0] if isinstance(cache, tuple) else cache
    limit = first.block_table.shape[1] * first.page_size
    if not isinstance(pos, jax.core.Tracer):
        hi = int(np.max(np.asarray(pos))) + kk
        if hi > limit:
            raise ValueError(
                f"block reaching position {hi} out of range (block table "
                f"covers {limit} = {first.block_table.shape[1]} pages x "
                f"{first.page_size})"
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
