"""The depth-bounded paged read (serve/paged_cache.bounded_read, PR 29)
+ int8 decode-weight GEMVs (ISSUE 12): parity of the bounded read, its
loop forced on at tiny sizes, with the whole-table gather + attend_kv —
a few f32 ulp in f32 and int8, the probabilities' rounding in bf16 —
across MHA/GQA/MQA and decode/prefill query widths, the paged-layout
edge cases the gather hides, depths on every boundary, and the
quantized-weight error bound. The GEMV runs in Pallas interpret mode on
CPU (tier-1 scope); chip_smoke.py makes the same comparisons on the
TPU. Until PR 29 these cases held the Pallas paged kernel to the
gather; the kernel lost to the gather at every shape on the chip and
went (ROADMAP D9), and each case now holds the read that replaced it.
Since PR 31 the latent layout's read (bounded_read_latent) is bounded
the same way, and the cases that are the same case take the layout as
one more head mapping, "latent": one 128-lane row a token that all
heads read, the absorbed products, f32 and bf16 rows.

Bitwise equality is kept for what it can promise: the same program run
twice (the masked-row poison checks below, the engine's greedy token
streams). Two different formulations of one sum get a band.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_cnn_tpu.models.generate import (
    _quant_kv,
    decode_step,
    generate,
    init_cache,
    pick_cache_dtype,
    pick_weights_dtype,
)
from mpi_cuda_cnn_tpu.models.transformer import LatentAttn, TransformerLM
from mpi_cuda_cnn_tpu.ops.pallas_gemv import (
    QuantW,
    dequantize_weight,
    int8_gemv,
    qmatmul,
    quantize_decode_params,
    quantize_weight,
)
from mpi_cuda_cnn_tpu.serve import paged_cache
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine
from mpi_cuda_cnn_tpu.serve.paged_cache import (
    init_paged_cache,
    paged_forward,
    paged_update_attend,
    paged_update_attend_latent,
    pages_for,
    read_step,
)
from mpi_cuda_cnn_tpu.serve.scheduler import Request

MODEL = TransformerLM(vocab=13, dim=32, heads=4, depth=2, max_seq=48)
GQA = TransformerLM(vocab=13, dim=32, heads=4, depth=2, max_seq=48,
                    kv_heads=2, pos="rope")
MQA = TransformerLM(vocab=13, dim=32, heads=4, depth=2, max_seq=48,
                    kv_heads=1, pos="rope")

HEAD_CONFIGS = {"mha": 4, "gqa": 2, "mqa": 1, "latent": "latent"}
# The latent layout at tiny widths: 4 heads over one row of 32 + 8
# values a token, stored 128 lanes wide (paged_cache.latent_row_lanes).
LATENT = LatentAttn(q_rank=16, kv_rank=32, nope=8, rope=8, v=8)
LANES = 128

# The cross-formulation band (ROADMAP D8). The bounded read folds blocks
# of pages as an online softmax does; the whole-table read runs one
# softmax over einsums whose reduction order is XLA:CPU's to choose (it
# changes between jax versions, which is how the former bitwise gate
# went red with no code change). Both compute in f32, so they agree to
# accumulated rounding:
# 32 ulp of the output's scale (3.8e-6) is several times the drift seen
# here and three orders tighter than a bf16 computation of the same
# case, whose operand rounding alone is 2^-9 = 2e-3.
F32_ULPS = 32


def _assert_f32_close(got, want, err_msg=""):
    scale = max(1.0, float(np.max(np.abs(want))))
    np.testing.assert_allclose(
        got, want, rtol=0,
        atol=F32_ULPS * np.finfo(np.float32).eps * scale, err_msg=err_msg)


def _rand_case(dtype, hkv, kk, seed, *, b=3, h=4, hd=8, ps=4, per=5,
               pool=16):
    """One random paged-attention call: q/k/v for the incoming tokens,
    a populated page pool, per-slot block tables of distinct non-scratch
    pages, and in-range positions. Returns (inputs..., call kwargs)."""
    rng = np.random.default_rng(seed)
    L = per * ps
    if hkv == "latent":
        # The same tuple with the layout's own parts: `k` is the
        # token's row, `v` the block's up-projections (_read tells the
        # layout by the pool's name).
        q, k, v = _latent_inputs(rng, b, kk, h, dtype)
        c = {"c": _latent_rows(rng, pool * ps, dtype).reshape(pool, ps, LANES)}
        table = np.stack([rng.choice(np.arange(1, pool), per, replace=False)
                          for _ in range(b)]).astype(np.int32)
        pos0 = rng.integers(0, L - kk, (b, 1))
        positions = jnp.asarray(pos0 + np.arange(kk)[None, :], jnp.int32)
        return q, k, v, c, jnp.asarray(table), positions, ps
    q = jnp.asarray(rng.normal(size=(b, kk, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, kk, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, kk, hkv, hd)), jnp.float32)
    kv = rng.normal(size=(2, pool, ps, hkv, hd)).astype(np.float32)
    if dtype == "int8":
        qk, sk = _quant_kv(jnp.asarray(kv[0]).reshape(1, pool * ps, hkv, hd))
        qv, sv = _quant_kv(jnp.asarray(kv[1]).reshape(1, pool * ps, hkv, hd))
        c = {"k": qk.reshape(pool, ps, hkv, hd),
             "ks": sk.reshape(pool, ps, hkv, 1),
             "v": qv.reshape(pool, ps, hkv, hd),
             "vs": sv.reshape(pool, ps, hkv, 1)}
    else:
        dt = jnp.dtype(dtype)
        c = {"k": jnp.asarray(kv[0], dt), "v": jnp.asarray(kv[1], dt)}
    table = np.zeros((b, per), np.int32)
    for i in range(b):
        table[i] = rng.choice(np.arange(1, pool), per, replace=False)
    pos0 = rng.integers(0, L - kk, (b, 1))
    positions = jnp.asarray(pos0 + np.arange(kk)[None, :], jnp.int32)
    return q, k, v, c, jnp.asarray(table), positions, ps


def _latent_inputs(rng, b, kk, h, dtype):
    """(q, the tokens' rows, the block's wuk / wuv) of a latent read."""
    a = LATENT
    q = jnp.asarray(rng.normal(size=(b, kk, h, a.nope + a.rope)), jnp.float32)
    row = jnp.asarray(rng.normal(size=(b, kk, 1, a.row)), jnp.float32)
    blk = {"wuk": jnp.asarray(rng.normal(size=(h, a.nope, a.kv_rank)) / 3,
                              dtype),
           "wuv": jnp.asarray(rng.normal(size=(h, a.kv_rank, a.v)) / 3,
                              dtype)}
    return q, row, blk


def _latent_rows(rng, n, dtype):
    """n stored rows: the row's values, zero lanes after them."""
    rows = np.zeros((n, LANES), np.float32)
    rows[:, :LATENT.row] = rng.normal(size=(n, LATENT.row))
    return jnp.asarray(rows, dtype)


# The loop at tiny sizes: blocks of 2 pages, 3 (slot, block) items a
# step -- paged_cache.read_step itself reads tables this small whole.
LOOP = (2, 3)


def _whole(slots, npages, page_size, key_bytes, **_):
    return npages, slots


def _loop(*a, **k):
    return LOOP


@pytest.fixture
def loop(monkeypatch):
    """Every paged read traced in the test takes the loop."""
    monkeypatch.setattr(paged_cache, "read_step", _loop)


def _read(step, c, q, k, v, positions, valid, table, ps):
    """(output, rows read) of one layer's write + read under `step`."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(paged_cache, "read_step", step)
        if "c" in c:        # the latent layout: k the row, v wuk / wuv
            o, _, n = paged_update_attend_latent(
                dict(c), q, k, positions, valid, table, ps, v, LATENT)
        else:
            o, _, n = paged_update_attend(dict(c), q, k, v, positions,
                                          valid, table, ps)
    return np.asarray(o), int(n)


def _both(q, k, v, c, table, positions, ps):
    """(whole-table gather + attend_kv, bounded read with its loop on)."""
    valid = jnp.ones(positions.shape, bool)
    args = (c, q, k, v, positions, valid, table, ps)
    return _read(_whole, *args)[0], _read(_loop, *args)[0]


@pytest.mark.parametrize("kk", [1, 4], ids=["decode", "chunk"])
@pytest.mark.parametrize("head", ["mha", "gqa", "mqa", "latent"])
def test_bounded_matches_full_f32(head, kk):
    """THE f32 gate: the bounded read's output equals the whole-table
    read's within the few-ulp band (F32_ULPS) — an indexing bug reads a
    wrong row and lands orders of magnitude outside it. Covers the
    decode tick (kk=1) and the chunked-prefill query width (kk=4) at
    every head mapping, and over latent rows (the whole-table read is
    attend_latent there, the loop folds inside itself)."""
    for seed in range(3):
        want, got = _both(*_rand_case("float32", HEAD_CONFIGS[head], kk,
                                      seed))
        _assert_f32_close(got, want, f"{head} kk={kk} seed={seed}")


# bf16 rows: K/V heads round the probabilities once (2 x 2^-8 of the
# scale covers both orders); the latent read also rounds the weighted
# latents to the weights' type before `wuv`, on both sides: twice that.
BF16_TOL = {"latent": 4 * 2.0 ** -8}


def _bf16_atol(head, want):
    return BF16_TOL.get(head, 2 * 2.0 ** -8) * max(
        1.0, float(np.max(np.abs(want))))


@pytest.mark.parametrize("kk", [1, 4], ids=["decode", "chunk"])
@pytest.mark.parametrize("dtype,head", [
    *[(d, h) for d in ("bfloat16", "int8") for h in ("mha", "gqa", "mqa")],
    ("bfloat16", "latent")])            # a latent pool is never int8
def test_bounded_matches_full_quantized(dtype, head, kk):
    """int8 pages: identical values and scales on both sides, scales
    applied outside the dots, everything after the convert in f32 — the
    1e-5 band of the existing quantized paged-vs-contiguous parity.
    bf16 pages: both reads round the PROBABILITIES to bf16 for the PV
    contraction, the whole-table read after normalising them and the
    bounded read before (exp(s - block max), folded afterwards), so
    the two differ by that rounding — at most 2^-8 relative per term,
    i.e. 2^-8 of the value scale on the output; the band is twice that
    (a wrong row is O(1) off)."""
    for seed in range(3):
        want, got = _both(*_rand_case(dtype, HEAD_CONFIGS[head], kk, seed))
        if dtype == "int8":
            tol = dict(rtol=1e-5, atol=1e-5)
        else:
            tol = dict(rtol=0, atol=_bf16_atol(head, want))
        np.testing.assert_allclose(
            got, want, **tol,
            err_msg=f"{dtype} {head} kk={kk} seed={seed}")


def _identity_paged_cache(model, batch, page_size, dtype=jnp.float32):
    per = pages_for(model.max_seq, page_size)
    cache = init_paged_cache(model, slots=batch,
                             num_pages=batch * per + 1,
                             page_size=page_size, dtype=dtype)
    table = 1 + np.arange(batch * per, dtype=np.int32).reshape(batch, per)
    return dataclasses.replace(cache, block_table=jnp.asarray(table))


@pytest.mark.parametrize("model", [MODEL, GQA], ids=["mha", "gqa_rope"])
def test_bounded_decode_step_matches_contiguous_f32(model, loop):
    """Transitivity of the layout contracts: bounded ~ whole-table (this
    file's f32 gate) and whole-table == contiguous (test_serve's), so
    decode_step over a paged cache read by the loop must match the
    contiguous cache through a 20-step decode, page, block and step
    boundaries crossed mid-sequence — logits within the same band (the
    per-layer drift passes through two blocks and the head, all f32)."""
    params = model.init(jax.random.key(0))
    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, 13, (3, 20)), jnp.int32
    )
    cc = init_cache(model, 3)
    pc = _identity_paged_cache(model, 3, page_size=8)
    # One jitted program per layout, traced once.
    step = jax.jit(lambda tok, pos, cache: decode_step(
        model, params, tok, pos, cache))
    for i in range(20):
        want, cc = step(toks[:, i], jnp.int32(i), cc)
        got, pc = step(toks[:, i], jnp.full((3,), i, jnp.int32), pc)
        _assert_f32_close(np.asarray(got), np.asarray(want), f"step {i}")


def test_slot_extent_ending_mid_page():
    """A slot whose extent ends mid-page must mask the page's written
    tail out of the softmax: corrupting rows BEYOND the slot's position
    (same page, later offsets) changes nothing; corrupting the position
    row itself does, in the whole-table read and in a block of the
    bounded one alike."""
    q, k, v, c, table, _, ps = _rand_case("float32", 2, 1, 7)
    # DISJOINT tables for this test: the poison targets one slot's page
    # tail, so no other slot may share that physical page.
    table = jnp.asarray(
        1 + np.arange(3 * 5, dtype=np.int32).reshape(3, 5) % 15)
    positions = jnp.asarray([[ps + 1], [2 * ps + 2], [1]], jnp.int32)
    want, got = _both(q, k, v, c, table, positions, ps)
    _assert_f32_close(got, want)
    # Poison the offsets just past each slot's position, inside the
    # same (mid-extent) page — outputs must not move.
    poisoned = dict(c)
    tab = np.asarray(table)
    for s, pos in enumerate(np.asarray(positions)[:, 0]):
        page = tab[s, pos // ps]
        off = pos % ps
        if off + 1 < ps:
            poisoned = {
                n: poisoned[n].at[page, off + 1:].set(1e30)
                if n in ("k", "v") else poisoned[n]
                for n in poisoned
            }
    want2, got2 = _both(q, k, v, poisoned, table, positions, ps)
    np.testing.assert_array_equal(got2, got)
    np.testing.assert_array_equal(want2, want)


def test_scratch_page_never_read():
    """Block-table columns beyond a slot's live pages hold 0 — the
    scratch page. Its contents are masked out of every softmax, so
    poisoning page 0 with huge finite values must not move any output
    (both reads alike). This is the page-0 contract the pool
    invariants assume."""
    q, k, v, c, table, _, ps = _rand_case("float32", 2, 1, 11)
    # Short extents: positions inside page 1 of 5, so table columns
    # 2..4 are dead weight — point them at scratch like the engine does.
    tab = np.asarray(table).copy()
    tab[:, 2:] = 0
    positions = jnp.asarray([[ps - 1], [2], [ps + 1]], jnp.int32)
    want, got = _both(q, k, v, c, jnp.asarray(tab), positions, ps)
    poisoned = {n: (c[n].at[0].set(1e30) if n in ("k", "v") else c[n])
                for n in c}
    want2, got2 = _both(q, k, v, poisoned, jnp.asarray(tab), positions, ps)
    np.testing.assert_array_equal(got2, got)
    np.testing.assert_array_equal(want2, want)


def test_cow_private_page_read_after_copy():
    """The COW discipline (ISSUE 9) on both reads: after a page is
    copied src -> dst and the slot's table repointed at dst, the read
    must see the COPY — later writes to the shared source must not
    leak into the reader. Mirrors engine.copy_page's per-layer
    .at[dst].set(c[src]) exactly."""
    q, k, v, c, table, positions, ps = _rand_case("float32", 2, 1, 13)
    tab = np.asarray(table).copy()
    src = int(tab[0, 0])
    dst = 15  # a free pool page outside every table
    assert not (tab == dst).any()
    copied = {n: c[n].at[dst].set(c[n][src]) for n in c}
    tab2 = tab.copy()
    tab2[0, 0] = dst
    want_before, got_before = _both(q, k, v, copied, jnp.asarray(tab2),
                                    positions, ps)
    _assert_f32_close(got_before, want_before)
    # Diverge the source AFTER the copy: the dst reader sees nothing.
    diverged = {n: (copied[n].at[src].set(-7.0) if n in ("k", "v")
                    else copied[n]) for n in copied}
    want_after, got_after = _both(q, k, v, diverged, jnp.asarray(tab2),
                                  positions, ps)
    np.testing.assert_array_equal(got_after, got_before)
    np.testing.assert_array_equal(want_after, want_before)


def test_preempted_then_resumed_slot_loop_on(loop):
    """Recompute preemption under a starved pool, with the bounded
    read's loop serving every read: the resumed slot re-prefills into DIFFERENT
    physical pages, and its greedy stream must still equal generate()'s
    — the block-table indirection is the only thing that changed."""
    params = MODEL.init(jax.random.key(1))
    rng = np.random.default_rng(5)
    engine = PagedEngine(MODEL, params, slots=3, num_pages=10, page_size=4,
                         prefill_chunk=8, max_len=40)
    prompts = [rng.integers(0, 13, (6,)).astype(np.int32) for _ in range(5)]
    want = [np.asarray(generate(MODEL, params, jnp.asarray(p[None, :]),
                                18))[0] for p in prompts]
    reqs = [Request(rid=i, prompt=p, max_new_tokens=18)
            for i, p in enumerate(prompts)]
    res = engine.run(reqs, mode="continuous")
    assert res.preemptions > 0
    for r in res.requests:
        np.testing.assert_array_equal(np.asarray(r.out), want[r.rid],
                                      err_msg=f"request {r.rid}")


def test_randomized_block_table_fuzz_bounded_equals_full():
    """Seeded fuzz over the block-table space: random pool sizes, page
    sizes, table permutations (slots may SHARE pages — the prefix-
    sharing read pattern), ragged per-slot depths, MHA/GQA/MQA — the
    bounded read vs the whole-table one inside the f32 band (F32_ULPS),
    every draw."""
    rng = np.random.default_rng(1234)
    for trial in range(12):
        hkv = int(rng.choice([1, 2, 4]))
        ps = int(rng.choice([2, 4, 8]))
        per = int(rng.integers(2, 6))
        pool = per * 3 + 2
        b = int(rng.integers(1, 4))
        kk = int(rng.choice([1, 2]))
        L = per * ps
        q = jnp.asarray(rng.normal(size=(b, kk, 4, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, kk, hkv, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, kk, hkv, 8)), jnp.float32)
        c = {"k": jnp.asarray(rng.normal(size=(pool, ps, hkv, 8)),
                              jnp.float32),
             "v": jnp.asarray(rng.normal(size=(pool, ps, hkv, 8)),
                              jnp.float32)}
        # Pages drawn WITH replacement across slots: shared pages are
        # legal reads (refcounted prefix pages).
        table = jnp.asarray(
            rng.integers(1, pool, (b, per)), jnp.int32)
        positions = jnp.asarray(
            rng.integers(0, L - kk + 1, (b, 1))
            + np.arange(kk)[None, :], jnp.int32)
        want, got = _both(q, k, v, c, table, positions, ps)
        _assert_f32_close(got, want, f"trial {trial}: hkv={hkv} ps={ps} "
                                     f"per={per} b={b} kk={kk}")


def _boundary_case(dtype, hkv, kk, *, ps=4, per=24, h=4, hd=8, seed=3):
    """Slots whose depths sit on every boundary of the LOOP geometry
    (blocks of 2 pages = 8 keys, 3 items a step), dead rows between
    live ones, one slot at max_len. Returns the call's inputs over a
    CLEAN pool and the same pool with garbage wherever no read may
    look: huge finite values in the rows of a slot's last block past
    its depth (read and masked), NaN in every page past that block
    (never read: a NaN row times a zero probability is NaN)."""
    rng = np.random.default_rng(seed)
    width = LOOP[0] * ps
    length = per * ps
    # first position of each slot's rows; None = a dead row
    starts = [0, None, ps - 1, ps, ps + 1, None, width - 1, width,
              width + 1, None, None, 3 * width - 1, 3 * width,
              length - kk - ps, length - kk]
    starts = [None if p is None else max(0, min(p, length - kk))
              for p in starts]
    b = len(starts)
    pool = b * per + 1
    live = np.array([p is not None for p in starts])
    pos0 = np.array([p or 0 for p in starts])
    positions = pos0[:, None] + np.arange(kk)[None, :]
    table = np.where(live[:, None],
                     1 + np.arange(b * per).reshape(b, per), 0)
    latent = hkv == "latent"
    if latent:
        q, k, v = _latent_inputs(rng, b, kk, h, dtype)
        kv = np.asarray(_latent_rows(rng, pool * ps, "float32"))[None]
    else:
        q = jnp.asarray(rng.normal(size=(b, kk, h, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, kk, hkv, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, kk, hkv, hd)), jnp.float32)
        kv = rng.normal(size=(2, pool * ps, hkv, hd)).astype(np.float32)
    dirty = kv.copy()
    for i in np.flatnonzero(live):
        depth = positions[i, -1]
        rows = table[i, :, None] * ps + np.arange(ps)[None, :]
        rows = rows.reshape(-1)                 # the slot's rows in order
        last = (depth // width + 1) * width
        dirty[:, rows[depth + 1:last]] = 1e30
        dirty[:, rows[last:]] = np.nan
    if latent:
        # Scratch page 0 too: the latent read gives a dead slot no item,
        # so nothing the mask admits lies there (the K/V read's dead
        # rows attend scratch row 0, and their output is nobody's).
        dirty[:, :ps] = 1e30

    def pools(rows):
        if latent:
            return {"c": jnp.asarray(rows[0], dtype).reshape(pool, ps, LANES)}
        if dtype == "int8":
            out = {}
            for name, r in zip("kv", rows):
                bad = ~np.isfinite(r) | (np.abs(r) > 1e20)
                qr, sr = _quant_kv(jnp.asarray(np.where(bad, 0.0, r))[None])
                mark = jnp.asarray(bad.any(axis=-1, keepdims=True))[None]
                nan = jnp.asarray(np.isnan(r).any(axis=-1, keepdims=True))
                sr = jnp.where(mark, jnp.where(nan[None], jnp.nan, 1e30), sr)
                qr = jnp.where(mark, jnp.int8(127), qr)
                out[name] = qr.reshape(pool, ps, hkv, hd)
                out[name + "s"] = sr.reshape(pool, ps, hkv, 1)
            return out
        return {name: jnp.asarray(r, dtype).reshape(pool, ps, hkv, hd)
                for name, r in zip("kv", rows)}

    args = (q, k, v, jnp.asarray(positions, jnp.int32),
            jnp.asarray(np.broadcast_to(live[:, None], (b, kk))),
            jnp.asarray(table, jnp.int32), ps)
    return pools(kv), pools(dirty), args, live, positions


@pytest.mark.parametrize("kk", [1, 32], ids=["decode", "chunk32"])
@pytest.mark.parametrize("dtype,head", [
    *[(d, h) for d in ("float32", "bfloat16", "int8")
      for h in ("mha", "gqa", "mqa")],
    ("float32", "latent"), ("bfloat16", "latent")])
def test_bounded_read_on_every_boundary_ignores_what_it_may_not_touch(
        dtype, head, kk):
    """Depths at 0, one under / at / one over a page, a block and a
    step boundary, a slot at max_len, dead rows between live ones: the
    bounded read over a pool full of garbage past every slot's depth
    equals the whole-table read over the clean pool, in the band of its
    type (F32_ULPS in f32; int8's 1e-5; bf16's probability rounding,
    2 x 2^-8 of the scale), and it touched no block past a slot's
    depth: rows read = steps x rows a step, by hand. Latent rows: the
    same depths, scratch page 0 poisoned as well, and a dead slot has
    no item (K/V: one block)."""
    clean, dirty, args, live, positions = _boundary_case(
        dtype, HEAD_CONFIGS[head], kk)
    want, n_whole = _read(_whole, clean, *args)
    got, n = _read(_loop, dirty, *args)
    per_block, per_step = LOOP
    ps, width = args[-1], per_block * args[-1]
    assert n_whole == len(live) * args[-2].shape[1] * ps
    dead = 0 if head == "latent" else 1
    items = sum(int(positions[i, -1]) // width + 1 if live[i] else dead
                for i in range(len(live)))
    assert n == -(-items // per_step) * per_step * width
    assert n < n_whole / 2
    want, got = want[live], got[live]           # a dead row's output is
    assert np.isfinite(got).all()               # nobody's
    if dtype == "float32":
        _assert_f32_close(got, want)
    elif dtype == "int8":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=_bf16_atol(head, want))


def test_the_step_comes_from_the_bytes_the_table_moves():
    """read_step at the benchmark's two tables (PERF.md section 4):
    chat's bf16 MHA rows (16 KB a key, 268 MB a layer) are read in
    blocks of 128 keys, 4 a step; generation's int8 MQA rows (264 B a
    key, 4 MB a layer) in one step, which is the whole-table read. A
    longer table of the same rows takes the loop."""
    chat = read_step(8, 128, 16, 2 * 32 * 128 * 2)
    assert chat == (8, 4) and chat[1] < 8 * (128 // chat[0])
    per_block, per_step = read_step(16, 64, 16, 2 * (128 + 4))
    assert per_block == 64 and per_step >= 16         # every block at once
    per_block, per_step = read_step(16, 512, 16, 2 * (128 + 4))
    assert per_step < 16 * -(-512 // per_block)


def test_the_latent_step_weighs_what_a_shared_row_costs():
    """read_step at the benchmark's latent table (PERF.md section 4:
    64 slots x 128 pages x 16 rows of 640 bf16 lanes, 128 heads), as
    paged_update_attend_latent asks it: a row's bytes, the operations
    of the H*kk query rows that all read it, the f32 output an item
    leaves. The tick (1 query a slot) takes the loop in blocks of a few
    hundred rows -- not the 832 the byte rule alone gives a 1,280-byte
    row -- several items a step; the 32-row chunk (one slot, 4,096
    query rows: a row's operations are 30 x its bytes, an item leaves
    8 MB) takes it in lane-tile blocks, one item a step. The K/V
    tables pass neither weight and keep their steps: chat's (8, 4),
    generation's whole read."""
    def latent(slots, kk, heads=128, lanes=640, rank=512):
        return read_step(slots, 128, 16, lanes * 2,
                         key_flops=2 * heads * kk * (lanes + rank),
                         stat_bytes=heads * kk * rank * 4)

    per_block, per_step = latent(64, 1)
    assert 128 <= per_block * 16 <= 512 and 4 <= per_step <= 16
    assert per_step < 64 * -(-128 // per_block)          # the loop
    assert read_step(64, 128, 16, 640 * 2)[0] * 16 == 832
    assert latent(1, 32) == (8, 1)
    # A table the tiny presets use is read whole whatever a row weighs.
    per_block, per_step = read_step(3, 20, 8, 128 * 4, key_flops=2 * 4 * 160,
                                    stat_bytes=4 * 32 * 4)
    assert per_step >= 3 * -(-20 // per_block)
    assert read_step(8, 128, 16, 2 * 32 * 128 * 2) == (8, 4)
    assert read_step(1, 128, 16, 2 * 32 * 128 * 2) == (8, 4)
    for slots in (16, 1):
        per_block, per_step = read_step(slots, 64, 16, 2 * (128 + 4))
        assert per_block == 64 and per_step >= slots


def test_kv_rows_read_is_the_hand_count_on_three_slots(loop):
    """paged_forward's count over a three-slot tick: slot 0 at position
    17 (3 blocks of 8 keys), slot 1 dead (1), slot 2 at 8 (2): 6 items
    = 2 steps of 3, 8 keys each, in each of the model's 2 layers."""
    params = MODEL.init(jax.random.key(0))
    cache = init_paged_cache(MODEL, slots=3, num_pages=3 * 12 + 1,
                             page_size=4)
    table = 1 + np.arange(36, dtype=np.int32).reshape(3, 12)
    table[1] = 0
    cache = dataclasses.replace(cache, block_table=jnp.asarray(table))
    _, cache = paged_forward(
        MODEL, params, jnp.zeros((3, 1), jnp.int32),
        jnp.asarray([[17], [0], [8]], jnp.int32),
        jnp.asarray([[True], [False], [True]]), cache)
    assert np.asarray(cache.counts).tolist() == [2 * 2 * 3 * 8]


def _mixed_requests(rng, lens, new):
    return [Request(rid=i, prompt=rng.integers(0, 13, (n,)).astype(np.int32),
                    max_new_tokens=m) for i, (n, m) in enumerate(zip(lens, new))]


def test_depths_cross_every_step_boundary_in_one_tick_and_one_prefill(loop):
    """The bound is a value inside the program: a run whose slots grow
    from 0 to max_len, across every page, block and step boundary,
    compiles nothing after the engine's first tick and first chunk,
    and its tick records carry `kv_rows_read` where a tick decoded --
    never more than the whole table, and less while slots are short."""
    params = MODEL.init(jax.random.key(2))
    eng = PagedEngine(MODEL, params, slots=3, num_pages=3 * 12 + 1,
                      page_size=4, prefill_chunk=8, max_len=48)
    rng = np.random.default_rng(11)
    eng.run(_mixed_requests(rng, [3], [2]))              # compiles both
    warm = eng.compiled_programs()
    assert warm == 2
    ticks = []
    res = eng.run(_mixed_requests(rng, [1, 9, 30, 17, 5], [47, 39, 18, 8, 3]),
                  tick_sink=ticks.append)
    assert res.status_counts() == {"finished": 5}
    assert eng.compiled_programs() == warm
    assert all(t["compiled"] == 0 for t in ticks)
    rows = [t["kv_rows_read"] for t in ticks if t["decoded"]]
    assert rows and all("kv_rows_read" not in t for t in ticks
                        if not t["decoded"])
    whole = MODEL.depth * 3 * 48
    assert max(rows) <= whole + MODEL.depth * 2 * 8 and min(rows) < whole / 2
    assert len(set(rows)) > 3                            # it follows depth


def test_counts_are_fetched_by_a_sink_and_by_nothing_else(loop):
    """With no sink and no registry the tick's counts stay on the
    device: run() never turns them into host values."""
    class NotForTheHost:
        def __array__(self, *a, **kw):
            raise AssertionError("the tick's counts were fetched")

    params = MODEL.init(jax.random.key(2))
    eng = PagedEngine(MODEL, params, slots=2, num_pages=2 * 12 + 1,
                      page_size=4, prefill_chunk=8, max_len=48)
    tick = eng._tick

    def counted(*args):
        cache, nxt = tick(*args)
        return dataclasses.replace(cache, counts=NotForTheHost()), nxt

    counted._cache_size = tick._cache_size
    eng._tick = counted
    rng = np.random.default_rng(12)
    res = eng.run(_mixed_requests(rng, [5, 9], [4, 6]))
    assert res.status_counts() == {"finished": 2}
    with pytest.raises(AssertionError, match="counts were fetched"):
        eng.run(_mixed_requests(rng, [5], [4]), tick_sink=lambda t: None)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_engine_greedy_matches_generate_loop_on(dtype, loop):
    """End-to-end engine-vs-generate greedy equality with the bounded
    read's loop in both jitted programs (prefill chunks AND decode
    ticks), across cache dtypes and both scheduler modes — the same
    acceptance the whole-table read of a small table holds in
    test_serve.py."""
    params = MODEL.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 13, (n,)).astype(np.int32)
               for n in (3, 7, 11, 5)]
    new = [9, 4, 12, 7]
    want = [
        np.asarray(generate(MODEL, params, jnp.asarray(p[None, :]), n,
                            cache_dtype=dtype))[0]
        for p, n in zip(prompts, new)
    ]
    engine = PagedEngine(MODEL, params, slots=2, num_pages=4 * 6 + 1,
                         page_size=8, prefill_chunk=4, cache_dtype=dtype)
    for mode in ("continuous", "static"):
        reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
                for i, (p, n) in enumerate(zip(prompts, new))]
        res = engine.run(reqs, mode=mode)
        for r in res.requests:
            np.testing.assert_array_equal(
                np.asarray(r.out), want[r.rid],
                err_msg=f"{mode} request {r.rid} ({dtype})")


def test_engine_vs_generate_with_both_levers_on(loop):
    """THE both-levers acceptance: the bounded read's loop + int8 decode
    weights in the engine, against generate() running the SAME
    quantized params over the contiguous cache — greedy streams equal
    per request (one forward implementation, two storage formats)."""
    params = GQA.init(jax.random.key(3))
    qparams = quantize_decode_params(params, "int8")
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 13, (n,)).astype(np.int32)
               for n in (4, 9, 6)]
    new = [8, 5, 11]
    want = [np.asarray(generate(GQA, qparams, jnp.asarray(p[None, :]), n,
                                cache_dtype="int8"))[0]
            for p, n in zip(prompts, new)]
    engine = PagedEngine(GQA, params, slots=2, num_pages=4 * 6 + 1,
                         page_size=8, prefill_chunk=4, cache_dtype="int8",
                         weights_dtype="int8")
    assert engine.weights_dtype == "int8"
    assert isinstance(engine.params["head"], QuantW)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, new))]
    res = engine.run(reqs, mode="continuous")
    for r in res.requests:
        np.testing.assert_array_equal(np.asarray(r.out), want[r.rid],
                                      err_msg=f"request {r.rid}")


def test_int8_weights_logit_error_bound():
    """int8 decode weights hold the same error discipline as the int8
    KV cache (test_generate's 5e-2 pin): per-channel absmax bounds each
    weight's relative error by 1/254 and the scales are exact f32
    multiplies outside the dots, so cached decode logits stay within
    the quantization band of the f32-weight path at every step."""
    params = MODEL.init(jax.random.key(0))
    qparams = quantize_decode_params(params, "int8")
    assert isinstance(qparams["blocks"][0]["wqkv"], QuantW)
    assert qparams["blocks"][0]["wqkv"].q.dtype == jnp.int8
    # Non-GEMV leaves stay untouched (gathers/layernorms).
    assert qparams["tok_emb"].dtype == jnp.float32
    assert qparams["blocks"][0]["ln1"]["g"].dtype == jnp.float32
    toks = jnp.asarray(
        np.random.default_rng(3).integers(0, 13, (2, 12)), jnp.int32
    )
    c32 = init_cache(MODEL, 2)
    c8 = init_cache(MODEL, 2)
    # Jitted once per weight format (un-jitted, every step re-traces
    # and recompiles the interpreted GEMV).
    step = jax.jit(lambda p, tok, pos, cache: decode_step(
        MODEL, p, tok, pos, cache))
    for i in range(12):
        l32, c32 = step(params, toks[:, i], jnp.int32(i), c32)
        l8, c8 = step(qparams, toks[:, i], jnp.int32(i), c8)
        np.testing.assert_allclose(np.asarray(l8), np.asarray(l32),
                                   rtol=5e-2, atol=5e-2,
                                   err_msg=f"step {i}")


def test_int8_gemv_matches_dequantized_matmul():
    """The fused GEMV's contract is (x @ q) * s — the scale stays
    OUTSIDE the contraction (the absmax discipline; it is constant
    along the contracted din). Pin it against the same jnp formulation
    within the f32 band (F32_ULPS: the kernel's din-tiled accumulation
    and XLA:CPU's dot order the same sum differently), and against the
    scale-inside dequantized matmul within the reassociation band,
    across tile counts on both axes (dout 128-divisible and not, the
    latter also wider than the tile cap; din one tile and several)."""
    rng = np.random.default_rng(0)
    for n, din, dout in ((8, 64, 256), (3, 32, 48), (1, 128, 128),
                         (4, 4096, 640), (8, 1024, 8192), (2, 256, 5000)):
        x = jnp.asarray(rng.normal(size=(n, din)), jnp.float32)
        w = quantize_weight(jnp.asarray(rng.normal(size=(din, dout)),
                                        jnp.float32))
        got = np.asarray(int8_gemv(x, w))
        want = np.asarray((x @ w.q.astype(jnp.float32)) * w.s)
        _assert_f32_close(got, want, f"{n}x{din}x{dout}")
        # Scale-inside (x @ dequant) reassociates one multiply — same
        # value to ~1 ulp of the accumulated dot.
        _assert_f32_close(got, np.asarray(x @ dequantize_weight(w)),
                          f"{n}x{din}x{dout} scale-inside")
        # qmatmul dispatch: QuantW routes to the kernel, arrays to @.
        np.testing.assert_allclose(np.asarray(qmatmul(x, w)), got,
                                   rtol=0, atol=0)
        plain = jnp.asarray(rng.normal(size=(din, dout)), jnp.float32)
        np.testing.assert_array_equal(np.asarray(qmatmul(x, plain)),
                                      np.asarray(x @ plain))


@pytest.mark.parametrize("din", [384, 8192], ids=["din1tile", "din4tiles"])
@pytest.mark.parametrize("n", [1, 3, 8, 16, 24, 32, 48])
def test_int8_gemv_keeps_all_three_bf16_terms_of_x(n, din):
    """The kernel contracts bf16 operands in one MXU pass, and is still
    an f32 GEMV: against an f64 (x @ q) * s it stays inside the f32
    band (F32_ULPS) for an x with a wide range of exponents, at every
    row count the engine can send (slots, the prefill chunk, a
    verify's rows) and with din one tile and several. bf16(x) alone —
    what is left if a later change drops `mid` and `lo` — is OUTSIDE
    the band through the same reference, so that change fails here and
    not in the benchmark's `correct`."""
    rng = np.random.default_rng(1000 * n + din)
    dout = 640
    x = (rng.normal(size=(n, din))
         * np.exp(2 * rng.normal(size=(n, din)))).astype(np.float32)
    w = QuantW(
        q=jnp.asarray(rng.integers(-127, 128, size=(din, dout)), jnp.int8),
        s=jnp.asarray(rng.uniform(0.5, 2.0, size=(1, dout)), jnp.float32))
    q64 = np.asarray(w.q, np.float64)
    s64 = np.asarray(w.s, np.float64)
    want = (x.astype(np.float64) @ q64) * s64
    _assert_f32_close(np.asarray(int8_gemv(jnp.asarray(x), w)), want,
                      f"{n}x{din}")
    hi = np.asarray(jnp.asarray(x).astype(jnp.bfloat16), np.float64)
    with pytest.raises(AssertionError):
        _assert_f32_close((hi @ q64) * s64, want)


def test_quantize_weight_error_bound():
    """Per-channel absmax: every dequantized weight within
    max|w_col|/254 of the original, per column."""
    rng = np.random.default_rng(2)
    w = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
    qw = quantize_weight(w)
    err = np.abs(np.asarray(dequantize_weight(qw)) - np.asarray(w))
    bound = np.max(np.abs(np.asarray(w)), axis=0) / 254.0 + 1e-7
    assert (err <= bound[None, :]).all()


def test_pick_weights_dtype_routing_shares_table_with_cache():
    """The two auto routers live on ONE table (_AUTO_DTYPE_ROUTING):
    weights route int8 under GQA/MQA (weight stream dominates once the
    cache is int8) and float32 at MHA (measured bf16-weights non-win);
    cache routes int8/bfloat16 as banked. Explicit dtypes pass through
    both."""
    from mpi_cuda_cnn_tpu.models.generate import _AUTO_DTYPE_ROUTING

    assert set(_AUTO_DTYPE_ROUTING) == {"cache", "weights"}
    assert pick_weights_dtype("auto", heads=8, kv_heads=2) == "int8"
    assert pick_weights_dtype("auto", heads=8, kv_heads=1) == "int8"
    assert pick_weights_dtype("auto", heads=8, kv_heads=None) == "float32"
    assert pick_weights_dtype("auto", heads=8, kv_heads=8) == "float32"
    assert pick_weights_dtype("bfloat16", heads=8, kv_heads=1) == "bfloat16"
    assert pick_cache_dtype("auto", heads=8, kv_heads=2) == "int8"
    assert pick_cache_dtype("auto", heads=8, kv_heads=None) == "bfloat16"


def test_bad_weights_dtype_rejected():
    params = MODEL.init(jax.random.key(0))
    with pytest.raises(ValueError, match="decode weights dtype"):
        PagedEngine(MODEL, params, slots=1, num_pages=4, page_size=4,
                    weights_dtype="fp8")
