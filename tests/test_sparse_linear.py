"""Softmax layers that SELECT the blocks they read beside linear layers
whose state is no page (ISSUE 34), against the plain reference of the
benchmark's `sparse_linear` family, which is loaded by its path and
imports nothing of the program. Tiny widths (dim 64, 4 query / 1 K/V
heads of 16; layers [sparse, linear x 3]; compressed keys of 4 rows
every 2, blocks of 8, top 2, window 16, dense under 32; pages of 4,
chunks of 8), seeded weights, f32, on the CPU.

The model:
1. Chunked prefill (a prompt that is no multiple of the chunk: padding
   rows) then ticks through the page pool and the slot's states give
   the reference's full-forward logits to 2e-4 absolute on logits of
   scale 0.5 (f32 on both sides: sums in other orders, nothing else),
   100 positions deep: three times `dense_len`. Read whole (as the code
   reads tables this small), by the loop (a tick then WALKS its chosen
   blocks), and by the loop's running fold.
2. The chunked linear form equals the token recurrence, with padding
   rows anywhere; the reference's own blocks of rows do too.
3. The blocks chosen equal the reference's at every depth, across page
   boundaries; the compressed pool is the mean-pool of the K rows.
4. Kept faults each FAIL the comparison: every block read, the
   lowest-scored blocks, a state not zeroed, a chunk that drops the
   carried state, a stale compressed key.

The engine:
5. It serves the reference's greedy tokens; a slot reused after a
   finish and after a preemption starts from a state that was poisoned
   and is zero again; storms leave the pool whole.
6. The tick record's counters by hand; no sink, nothing fetched; an
   older model's record has none of the new fields.
7. What a state that is no page cannot follow refuses, by mechanism.
"""

import dataclasses
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402
from benchmarks.tests.test_sparse_linear_tiny import (  # noqa: E402
    FAULTS,
    reads_every_block,
    stale_compressed_keys,
    takes_the_lowest_blocks,
)
from mpi_cuda_cnn_tpu.faults import FaultInjector  # noqa: E402
from mpi_cuda_cnn_tpu.models.generate import linear_attend  # noqa: E402
from mpi_cuda_cnn_tpu.models.transformer import (  # noqa: E402
    LinearAttn,
    SparseSelect,
    TransformerLM,
)
from mpi_cuda_cnn_tpu.serve import paged_cache  # noqa: E402
from mpi_cuda_cnn_tpu.serve.core import build_scheduler  # noqa: E402
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine  # noqa: E402
from mpi_cuda_cnn_tpu.serve.paged_cache import (  # noqa: E402
    SlotStates,
    bounded_read,
    init_paged_cache,
    init_slot_states,
    paged_forward,
    paged_update_attend,
    select_blocks,
)
from mpi_cuda_cnn_tpu.serve.scheduler import Request  # noqa: E402

BENCH = ROOT / "benchmarks"
TINY = BENCH / "tests" / "tiny_sparse_linear"
SEED = 2**31 + 34
FAM = run.load_family(BENCH / "families" / "sparse_linear")
PAGE, CHUNK, MAX_LEN = 4, 8, 128
LOOP = (2, 3)       # blocks of 2 pages, 3 (slot, block) items a step


def tiny_cfg(**over):
    cfg = json.loads((TINY / "configs" / "tiny-sala.json").read_text())
    return {**cfg, "vocab_size": 96, "weights_dtype": "float32",
            "cache_dtype": "float32", **over}


def build(**over):
    cfg = tiny_cfg(**over)
    dm = FAM.weights.dims(cfg)
    return cfg, dm, FAM.build.model_of(dm), FAM.build.serving_params(
        dm, SEED, cfg)


@pytest.fixture(scope="module")
def served():
    return build()


@pytest.fixture(autouse=True)
def fresh_traces():
    """bounded_read is jitted by itself and keeps its traces by shape
    and step; tests patch what it reads at trace time."""
    bounded_read.clear_cache()
    yield
    bounded_read.clear_cache()


def force_read(monkeypatch, read):
    """`whole`: the code's own choice at these sizes. `loop`: the
    bounded read's loop (one row a slot then walks its chosen blocks).
    `running`: the loop with the running fold of a slot with many
    query rows."""
    if read != "whole":
        monkeypatch.setattr(paged_cache, "read_step", lambda *a, **k: LOOP)
    if read == "running":
        monkeypatch.setattr(paged_cache, "_many_queries", lambda q: True)


def serve_sequence(model, params, seq, n_prompt, *, states=None):
    """Logits of every position of `seq`: its first `n_prompt` tokens
    by prefill chunks, the rest by decode ticks, in slot 1 of 2 (slot 0
    dead). `states`: what the slots' states hold before the first
    chunk (default zeros)."""
    cache = init_paged_cache(model, slots=2, num_pages=MAX_LEN // PAGE + 1,
                             page_size=PAGE, max_len=MAX_LEN)
    table = np.zeros((2, MAX_LEN // PAGE), np.int32)
    table[1] = np.arange(1, MAX_LEN // PAGE + 1)
    cache = dataclasses.replace(cache, block_table=jnp.asarray(table))
    store = SlotStates(states=states or init_slot_states(model, 2),
                       rows=jnp.arange(2, dtype=jnp.int32))
    program = jax.jit(functools.partial(paged_forward, model))
    got, caches = {}, (cache, store)

    def forward(toks, pos, valid):
        nonlocal caches
        logits, caches = program(params, jnp.asarray(toks), jnp.asarray(pos),
                                 jnp.asarray(valid), caches)
        return logits

    for start in range(0, n_prompt, CHUNK):
        n = min(CHUNK, n_prompt - start)
        toks = np.zeros((2, CHUNK), np.int32)
        toks[1, :n] = seq[start:start + n]
        pos = start + np.arange(CHUNK)[None, :] * np.ones((2, 1), np.int32)
        valid = np.zeros((2, CHUNK), bool)
        valid[1, :n] = True
        logits = forward(toks, pos, valid)
        got.update({start + j: logits[1, j] for j in range(n)})
    for p in range(n_prompt, len(seq)):
        logits = forward(np.array([[0], [seq[p]]], np.int32),
                         np.array([[0], [p]], np.int32),
                         np.array([[False], [True]]))
        got[p] = logits[1, 0]
    return np.stack([got[p] for p in range(len(seq))]), caches


# -- 1. the whole model against the reference ---------------------------------

N_PROMPT, N_TOTAL = 45, 100     # 45: five chunks and five padding rows


def sequence(dm):
    return np.random.default_rng(1).integers(0, dm["vocab"], N_TOTAL)


def reference(dm, seq):
    return FAM.reference.forward_logits(
        dm, SEED, [seq], [np.arange(len(seq))])[0][0]


@pytest.mark.parametrize("read", ["whole", "loop", "running"])
def test_prefill_then_paged_decode_matches_the_reference(served, read,
                                                         monkeypatch):
    _, dm, model, params = served
    force_read(monkeypatch, read)
    seq = sequence(dm)
    got, caches = serve_sequence(model, params, seq, N_PROMPT)
    want = reference(dm, seq)
    # f32 on both sides: the program sums its softmax block by block
    # and runs the recurrence a chunk at a time, the reference in
    # blocks of its own; 2e-4 on logits of scale 0.5 is that and
    # nothing else (a block chosen differently moves a logit by 1e-2).
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert float(jnp.std(want)) > 0.4
    rows, scored, gathered, nchosen, updated = np.asarray(
        caches[0].counts).tolist()
    # The last tick, position 99: 49 compressed keys are complete (4
    # rows every 2 of 100), the one K/V head chooses block 0, blocks
    # 10..12 (keys 84..99) and 2 more, and 3 linear layers wrote the
    # one live slot's state.
    assert (scored, nchosen, updated) == (49, 6, 3)
    if read == "whole":
        assert (rows, gathered) == (2 * MAX_LEN, 2 * MAX_LEN // 2)
    else:   # the live slot's 6 blocks and the dead slot's 1: 7 items of
        # 8 keys, 3 a step; the selection gathered the live slot's 49
        # compressed keys in blocks of 2 pages of 2: 13 items, the
        # dead slot none
        assert (rows, gathered) == (3 * 3 * 8, 13 * 4)


# -- 2. the recurrence ---------------------------------------------------------

def token_recurrence(q, k, v, log_decay):
    """S_t = l S_{t-1} + k_t^T v_t, o_t = q_t S_t / sqrt(hd), a token
    at a time in float64: q, k, v (T, H, hd)."""
    t, h, hd = q.shape
    lam = np.exp(np.asarray(log_decay, np.float64))
    s = np.zeros((h, hd, hd))
    out = np.zeros((t, h, hd))
    for i in range(t):
        s = lam[:, None, None] * s + np.einsum("hd,he->hde", k[i], v[i])
        out[i] = np.einsum("hd,hde->he", q[i], s) / np.sqrt(hd)
    return out, s


@pytest.mark.parametrize("chunk,pattern", [
    (8, "prefix"), (5, "prefix"), (1, "prefix"), (8, "holes")])
def test_the_chunked_linear_form_is_the_token_recurrence(chunk, pattern):
    """37 tokens (no multiple of any chunk here) through linear_attend
    `chunk` rows at a time, the last chunk padded; `holes`: invalid
    rows in the middle of a chunk too, which add nothing and decay
    nothing."""
    rng = np.random.default_rng(2)
    h, hd, t = 4, 16, 37
    q, k, v = (rng.standard_normal((t, h, hd)) for _ in range(3))
    ld = LinearAttn().log_decay(h)
    want, want_state = token_recurrence(q, k, v, ld)
    state = jnp.zeros((1, h, hd, hd), jnp.float32)
    got, at = [], 0
    while at < t:
        valid = np.ones(chunk, bool)
        if pattern == "holes" and chunk > 2:
            valid[[1, chunk - 2]] = False
        take = min(int(valid.sum()), t - at)
        valid &= np.cumsum(valid) <= take
        rows = np.zeros((3, chunk, h, hd))
        rows[:, valid] = np.stack([x[at:at + take] for x in (q, k, v)])
        rows[:, ~valid] = 1e3      # what a padding row holds is nobody's
        o, state = linear_attend(
            *(jnp.asarray(x[None], jnp.float32) for x in rows), state,
            jnp.asarray(valid[None]), ld)
        got.append(np.asarray(o)[0, valid].reshape(-1, h, hd))
        at += take
    np.testing.assert_allclose(np.concatenate(got), want, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state)[0], want_state, atol=2e-5)


def test_the_references_blocks_of_rows_are_the_token_recurrence(
        served, monkeypatch):
    _, dm, _, _ = served
    monkeypatch.setattr(FAM.reference, "STATE_BLOCK", 8)
    rng = np.random.default_rng(3)
    t, w, h, hd = 21, dm["width"], dm["heads"], dm["head_dim"]
    a = jnp.asarray(rng.standard_normal((t, w)), jnp.float32)
    blk = FAM.weights.block_f32(dm, FAM.weights.root_key(SEED), 1)
    with jax.default_matmul_precision("highest"):
        got = FAM.reference.linear_attention(dm, a, blk, np.arange(t))
        q, k = (FAM.reference._rotate(FAM.reference._rms(
            (a @ blk[m]).reshape(t, h, hd), blk[n], dm["eps"]),
            dm["rope_theta"]) for m, n in (("wq", "q_norm"), ("wk", "k_norm")))
        v = (a @ blk["wv"]).reshape(t, h, hd)
        o, _ = token_recurrence(*(np.asarray(x, np.float64) for x in (q, k, v)),
                                LinearAttn(dm["slope"]).log_decay(h))
        o = FAM.reference._rms(jnp.asarray(o, jnp.float32), blk["o_norm"],
                               dm["eps"]).reshape(t, h * hd)
        want = (o * jax.nn.sigmoid(a @ blk["wgate"])) @ blk["wo"]
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- 3. the selection ----------------------------------------------------------

@pytest.fixture(scope="module")
def written():
    """110 random K/V rows written through the program in chunks of 8
    (pages of 4: every chunk crosses page boundaries), one slot of a
    table of 32 pages: the pools and the rows."""
    sel = SparseSelect(kernel=4, stride=2, block=8, topk=2, init_blocks=1,
                       window=16, dense_len=32)
    rng = np.random.default_rng(4)
    t, hkv, h, hd = 110, 2, 4, 16
    q, k, v = (jnp.asarray(rng.standard_normal((t, n, hd)), jnp.float32)
               for n in (h, hkv, hkv))
    pools = {"k": jnp.zeros((33, PAGE, hkv, hd)), "v": jnp.zeros(
        (33, PAGE, hkv, hd)), "kc": jnp.zeros((33, PAGE // 2, hkv, hd))}
    table = jnp.asarray(np.random.default_rng(5).permutation(
        np.arange(1, 33))[None].astype(np.int32))
    for at in range(0, t, CHUNK):
        n = min(CHUNK, t - at)
        pad = lambda x: jnp.pad(x[at:at + n], (  # noqa: E731
            (0, CHUNK - n), (0, 0), (0, 0)))[None]
        _, pools, _, _ = paged_update_attend(
            pools, pad(q), pad(k), pad(v), (at + jnp.arange(CHUNK))[None],
            (jnp.arange(CHUNK) < n)[None], table, PAGE, select=sel)
    dm = {"select": (4, 2, 8, 2, 1, 16, 32)}
    return sel, dm, q, k, pools, table


def test_the_compressed_pool_is_the_mean_pool_of_the_k_rows(written):
    sel, dm, _, k, pools, table = written
    want = FAM.reference.compressed_keys(dm, k)             # (J, hkv, hd)
    assert want.shape[0] == (110 - 4) // 2 + 1
    got = pools["kc"][table[0]].reshape(-1, *want.shape[1:])
    np.testing.assert_allclose(got[: want.shape[0]], want, atol=1e-6)
    assert not np.any(np.asarray(got[want.shape[0]:]))      # none written early
    rows = np.arange(4)[None, :] + 2 * np.arange(want.shape[0])[:, None]
    np.testing.assert_allclose(want, np.asarray(k)[rows].mean(1), atol=1e-6)


def test_the_blocks_chosen_are_the_references_at_every_depth(written):
    sel, dm, q, k, pools, table = written
    t, h, hkv = q.shape[0], q.shape[1], k.shape[1]
    kc = FAM.reference.compressed_keys(dm, k)
    at = jnp.arange(t)
    want = FAM.reference.chosen_blocks(
        dm, q.reshape(t, hkv, h // hkv, -1), kc, at, 32 * PAGE // 8)
    got, scored, gathered, nchosen = select_blocks(
        q[None], pools["kc"], at[None], jnp.ones((1, t), bool), table, PAGE,
        sel)
    assert int(gathered) == 32 * PAGE // 2      # many rows: the table, whole
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))
    counts = np.asarray(want).sum(-1)                       # (hkv, t)
    # Under dense_len every block up to the query's; past it block 0,
    # the window's two or three, and two more.
    assert (counts[:, :31] == (np.arange(31) // 8 + 1)).all()
    there = np.arange(31, t) // 8 + 1
    assert (counts[:, 31:] >= np.minimum(there, 5)).all()
    assert (counts[:, 31:] <= np.minimum(there, 6)).all()
    assert set(counts[:, 48:].ravel()) == {5, 6}
    assert int(nchosen) == counts.sum()
    assert int(scored) == hkv * sum(max(p + 1 - 4 + 2, 0) // 2
                                    for p in range(t))


# A tick: slot -> its one row's position, None a slot with no valid row.
TICK = (None, 0, 20, None, 109, 63, 2, None, 31, 32)


def tick_of(written, pages: int, depths=TICK):
    """`depths` as one tick over tables of `pages` pages: slot s's
    query is row depths[s] of the fixture's, its compressed keys the
    ones complete at that depth, on pages of its own; EVERY other row
    of the pool -- scratch, the dead slots' pages, a live slot's pages
    past its last complete key -- is NaN."""
    sel, dm, q, k, _, _ = written
    kc = np.asarray(FAM.reference.compressed_keys(dm, k))   # (J, hkv, hd)
    cpp, b = PAGE // sel.stride, len(depths)
    held = 110 // PAGE + 1
    pool = np.full((1 + b * held, cpp) + kc.shape[1:], np.nan, np.float32)
    table = np.zeros((b, pages), np.int32)
    table[:, :held] = 1 + np.arange(b * held).reshape(b, held)
    for s, d in enumerate(depths):
        if d is not None:
            have = int(sel.compressed(d + 1))
            mine = pool[table[s, :held]].reshape(-1, *kc.shape[1:])
            mine[:have] = kc[:have]
            pool[table[s, :held]] = mine.reshape(held, cpp, *kc.shape[1:])
    live = np.array([d is not None for d in depths])
    at = np.array([d or 0 for d in depths])
    return (q[at][:, None], jnp.asarray(pool), jnp.asarray(at[:, None]),
            jnp.asarray(live[:, None]), jnp.asarray(table)), live, at


@pytest.mark.parametrize("table", ["patched_step", "large"])
def test_the_blocks_chosen_are_the_references_at_every_depth_of_a_tick(
        written, table, monkeypatch):
    """Ten slots at depths under and past dense_len (32), one at depth
    0, three dead. The loop by a patched step over tables of 32 pages
    (blocks of 2 pages = 4 compressed keys), and by the code's own
    choice over tables of 16,384 pages (blocks of 512 keys, 8 a step)."""
    sel, dm, q, k, _, _ = written
    hkv, g = k.shape[1], q.shape[1] // k.shape[1]
    pages = 32 if table == "patched_step" else 1 << 14
    args, live, at = tick_of(written, pages)
    whole = select_blocks(*args, PAGE, sel) if pages == 32 else None
    if table == "patched_step":
        monkeypatch.setattr(paged_cache, "read_step", lambda *a, **k: LOOP)
    step = paged_cache._index_step(len(TICK), pages, args[1])
    assert step[1] < len(TICK) * -(-pages // step[0])       # a loop's worth
    block = step[0] * PAGE // sel.stride
    got, scored, gathered, nchosen = jax.jit(
        lambda *a: select_blocks(*a, PAGE, sel))(*args)
    nb = pages * PAGE // sel.block
    want = FAM.reference.chosen_blocks(
        dm, q[at[live]].reshape(-1, hkv, g, q.shape[-1]),
        FAM.reference.compressed_keys(dm, k), jnp.asarray(at[live]), nb)
    np.testing.assert_array_equal(
        np.asarray(got)[live, :, 0], np.transpose(np.asarray(want), (1, 0, 2)))
    have = np.asarray(sel.compressed(at[live] + 1))
    assert int(scored) == hkv * have.sum()
    assert int(nchosen) == np.asarray(want).sum()
    # The live slots' keys in whole blocks; a slot with no complete key
    # (depths 0 and 2) and a dead slot: nothing.
    assert int(gathered) == (-(-have // block) * block).sum()
    assert int(gathered) < len(TICK) * pages * PAGE // sel.stride
    if whole is not None:       # ... and what the table read whole gives
        np.testing.assert_array_equal(np.asarray(got)[live],
                                      np.asarray(whole[0])[live])
        assert [int(x) for x in whole[1:]] == [
            int(scored), len(TICK) * 32 * PAGE // sel.stride, int(nchosen)]
    none = select_blocks(*args[:3], jnp.zeros_like(args[3]), args[4], PAGE,
                         sel)
    assert [int(x) for x in none[1:]] == [0, 0, 0]


def test_the_selections_step_at_the_benchmarks_table():
    """32 slots x 4,096 pages of one 512 B compressed key: a page
    gathered alone weighs 4 KB, so a slot's keys are rounded up to 256
    and a step takes 8 such blocks (PERF.md section 6, PR 35); a tiny
    preset's table is one step, gathered whole."""
    pool = jax.ShapeDtypeStruct((9, 1, 2, 128), jnp.bfloat16)
    assert paged_cache._index_step(32, 4096, pool) == (256, 8)
    tiny = jax.ShapeDtypeStruct((9, 2, 1, 16), jnp.float32)
    per_block, per_step = paged_cache._index_step(3, 32, tiny)
    assert per_step >= 3 * -(-32 // per_block)


def best_by_sort(far, k):
    """The form _best_blocks replaced: the k-th best by lax.top_k."""
    kth = jax.lax.top_k(far, k)[0][..., -1:]
    above, ties = far > kth, far == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (far >= 0) & (above | (ties & (jnp.cumsum(ties, axis=-1) <= room)))


def _random_far():
    rng = np.random.default_rng(6)
    far = rng.random((3, 2, 5, 64), np.float32).round(1)    # many ties
    return np.where(rng.random(far.shape) < 0.4, -1.0, far)


NORMAL = float(np.finfo(np.float32).tiny)   # the smallest normal f32
HARD = {
    "all_equal": ([[0.25] * 6], 3),
    "all_zero": ([[0.0] * 6], 3),
    "signed_zeros": ([[0.0, -0.0, 0.0, -0.0, -1.0, 0.5]], 3),
    "no_candidate": ([[-1.0] * 6], 2),
    "fewer_candidates_than_k": ([[-1.0, 0.3, -1.0, 0.0, -1.0, -1.0]], 4),
    "exactly_k": ([[0.2, -1.0, 0.7, -1.0, 0.2, -1.0]], 3),
    "k_is_every_block": ([[0.2, -1.0, 0.7, 0.0, 0.2, 0.1]], 6),
    "ties_across_the_kth_place": ([[0.5, 0.3, 0.3, 0.1, 0.3, -1.0, 0.3],
                                   [0.3, 0.3, 0.3, 0.3, 0.5, 0.5, 0.5]], 3),
    "smallest_normals": ([[NORMAL, 2 * NORMAL, 0.0, 3 * NORMAL, NORMAL,
                           -1.0]], 2),
    "denormals": ([[1e-45, 3e-45, 0.0, 1e-40, 1e-45, -1.0]], 2),
    "one_beside_minus_one": ([[1.0, -1.0, 1.0, -1.0, 0.5, 1.0]], 2),
    "largest_and_sums_past_one": ([[3.4e38, 16.0, 1.0, 1.0000001, 2.0]], 3),
    "random_k1": (_random_far(), 1),
    "random_k7": (_random_far(), 7),
    "random_k64": (_random_far(), 64),
}


@pytest.mark.parametrize("case", HARD)
def test_the_kth_best_score_without_a_sort_is_the_sorts(case):
    far, k = HARD[case]
    far = np.asarray(far, np.float32)
    got = np.asarray(jax.jit(paged_cache._best_blocks, static_argnums=1)(
        jnp.asarray(far), k))
    if case != "denormals":     # the float compares of the replaced form
        # read a denormal as zero on this backend; the integer keys order it
        np.testing.assert_array_equal(got, np.asarray(best_by_sort(
            jnp.asarray(far), k)))
    # ... and the definition, by hand: of the candidates the k first in
    # descending order of score, ties to the lower block.
    rank = np.argsort(np.argsort(-far, axis=-1, kind="stable"), axis=-1)
    np.testing.assert_array_equal(got, (far >= 0) & (rank < k))
    assert (got.sum(-1) == np.minimum(k, (far >= 0).sum(-1))).all()


# -- 4. kept faults (benchmarks/tests/test_sparse_linear_tiny.py's) ------------

@pytest.mark.parametrize("fault", FAULTS)
def test_a_kept_fault_fails_the_comparison(served, fault, monkeypatch):
    _, dm, model, params = served
    seq = sequence(dm)
    want = reference(dm, seq)
    fault(monkeypatch)
    dirty = [s + 3.0 for s in init_slot_states(model, 2)]
    got, _ = serve_sequence(model, params, seq, N_PROMPT, states=dirty)
    off = np.abs(got - np.asarray(want)).max(axis=-1)
    assert off.max() > 1e-2, off.max()      # fifty times the tolerance
    if fault in (reads_every_block, takes_the_lowest_blocks,
                 stale_compressed_keys):
        # ... and only where selection bites: under dense_len 32 the
        # faulty program IS the model (a stale key: until the first
        # tick's, at 45).
        clean = 31 if fault is not stale_compressed_keys else N_PROMPT
        assert off[:clean].max() < 2e-4 < off[clean:].max()


def test_a_poisoned_state_is_zero_again_at_position_0(served):
    """... and without a fault the same dirty states change nothing."""
    _, dm, model, params = served
    seq = sequence(dm)[:40]
    dirty = [s + jnp.nan for s in init_slot_states(model, 2)]
    got, caches = serve_sequence(model, params, seq, 21, states=dirty)
    np.testing.assert_allclose(got, reference(dm, seq), atol=2e-4, rtol=0)
    # The dead slot's state was never written: still what it held.
    assert all(bool(jnp.all(jnp.isnan(s[0])) & jnp.all(jnp.isfinite(s[1])))
               for s in caches[1].states)


# -- 5. the engine -------------------------------------------------------------

def engine(served, **kw):
    _, _, model, params = served
    kw = {"slots": 3, "num_pages": 3 * MAX_LEN // PAGE + 1,
          "cache_dtype": "float32", **kw}
    return PagedEngine(model, params, page_size=PAGE, prefill_chunk=CHUNK,
                       max_len=MAX_LEN, **kw)


LENS, NEW = (9, 70, 37, 5, 50, 12, 28), (30, 20, 8, 40, 12, 25, 6)


def requests(dm, lens=LENS, new=NEW):
    rng = np.random.default_rng(9)
    return [Request(rid=i, prompt=rng.integers(0, dm["vocab"], n).astype(
        np.int32), max_new_tokens=m) for i, (n, m) in enumerate(zip(lens, new))]


def outputs(res):
    return {r.rid: r.out for r in res.requests}


@pytest.fixture(scope="module")
def plain(served):
    eng = engine(served)
    ticks = []
    res = eng.run(requests(served[1]), tick_sink=ticks.append)
    assert res.status_counts() == {"finished": 7}
    return res, ticks


def test_the_engine_serves_the_references_greedy_tokens(served, plain):
    _, dm, _, _ = served
    for r in plain[0].requests:
        seq = np.zeros(MAX_LEN, np.int32)   # right-padding is harmless
        n = r.prompt.size + len(r.out)
        seq[:n] = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        rows = np.full(40, n - 2)           # one shape to compile
        rows[: len(r.out)] = np.arange(r.prompt.size - 1, n - 1)
        want = FAM.reference.forward_logits(dm, SEED, [seq], [rows])[0][0][
            : len(r.out)]
        gap = np.max(want, -1) - np.take_along_axis(
            np.asarray(want), np.asarray(r.out)[:, None], -1)[:, 0]
        assert float(gap.max()) < 1e-3      # the reference's own choices


@pytest.mark.parametrize("how", ["finish", "preempt"])
def test_a_reused_slot_starts_from_a_poisoned_then_zeroed_state(
        served, plain, how):
    """Seven requests through three slots: every slot is reused after a
    finish; with a pool too small for three, after a preemption too.
    Before the run every slot's state is poisoned; the tokens are the
    clean run's, and `state_resets` counts every start from zero."""
    _, dm, _, _ = served
    eng = engine(served, **({"num_pages": 40} if how == "preempt" else {}))
    eng._states = [s + jnp.nan for s in eng._states]
    ticks = []
    res = eng.run(requests(dm), tick_sink=ticks.append)
    assert res.status_counts() == {"finished": 7}
    assert (res.preemptions > 0) == (how == "preempt")
    assert outputs(res) == outputs(plain[0])
    assert sum(t["state_resets"] for t in ticks) == 7 + res.preemptions
    assert all(t["pages_held"] == [eng.num_pages - 1 - t["free_pages"]]
               for t in ticks)


@pytest.mark.parametrize("storm", ["squeeze", "expire", "static"])
def test_storms_leave_the_pool_whole(served, plain, storm):
    _, dm, _, _ = served
    reqs, run_kw = requests(dm), {}
    if storm == "squeeze":
        run_kw["faults"] = FaultInjector(
            "squeeze@serve.tick:3?pages=60&ticks=6;"
            "squeeze@serve.tick:25?pages=80&ticks=4")
    elif storm == "expire":
        for r in reqs[1::2]:
            r.deadline = 0.0 + 1e-3 * (r.rid + 1)
    else:
        run_kw["mode"] = "static"
    res = engine(served).run(reqs, **run_kw)    # run() proves the pool clean
    assert len(res.requests) == 7
    if storm == "expire":
        assert res.status_counts().get("expired", 0) > 0
    else:
        assert outputs(res) == outputs(plain[0])


# -- 6. the counters -----------------------------------------------------------

def test_the_tick_record_counts_the_selection_and_the_states(served, plain):
    _, dm, model, _ = served
    _, ticks = plain
    depth = {}
    seen = 0
    for t in ticks:
        if t["prefill"]:    # the chunk runs before the iteration's tick
            depth[t["prefill"][1]] = depth.get(t["prefill"][1], 0) + t[
                "prefill"][2]
        if t["decoded"]:
            seen += 1
            at = [depth[rid] for _, rid in t["decoded"]]
            assert t["state_slots_updated"] == 3 * len(at)
            assert t["index_rows_read"] == sum(
                max(p + 1 - 4 + 2, 0) // 2 for p in at)
            # Under dense_len every block to the depth; past it block
            # 0, the window's two or three and two more.
            low = sum(p // 8 + 1 if p + 1 < 32 else min(p // 8 + 1, 5)
                      for p in at)
            high = sum(p // 8 + 1 if p + 1 < 32 else min(p // 8 + 1, 6)
                       for p in at)
            assert low <= t["sparse_blocks_selected"] <= high
            assert t["kv_rows_read"] == 3 * MAX_LEN    # one table, whole
            # ... and its compressed keys, one every 2 rows, as whole
            assert t["index_rows_gathered"] == 3 * MAX_LEN // 2
        else:
            assert "state_slots_updated" not in t
        for _, rid in t["decoded"]:
            depth[rid] += 1
    assert seen > 20
    assert sum(t["state_resets"] for t in ticks) == 7


def test_counts_are_fetched_by_a_sink_and_by_nothing_else(served):
    class NotForTheHost:
        def __array__(self, *a, **kw):
            raise AssertionError("the tick's counts were fetched")

    eng = engine(served)
    tick = eng._tick

    def counted(*args):
        (cache, store), nxt = tick(*args)
        return (dataclasses.replace(cache, counts=NotForTheHost()),
                store), nxt

    counted._cache_size = tick._cache_size
    eng._tick = counted
    dm = served[1]
    res = eng.run(requests(dm, lens=(5, 9), new=(4, 6)))
    assert res.status_counts() == {"finished": 2}
    with pytest.raises(AssertionError, match="counts were fetched"):
        eng.run(requests(dm, lens=(5,), new=(4,)), tick_sink=lambda t: None)


def test_an_older_model_has_none_of_the_new_fields():
    model = TransformerLM(vocab=64, dim=32, heads=4, kv_heads=2, depth=2,
                          max_seq=64, pos="rope")
    assert model.cache_groups() == ((0, (0, 1)),)
    assert model.state_layers() == () and model.mixer(1) == "attn"
    eng = PagedEngine(model, model.init(jax.random.key(0)), slots=2,
                      num_pages=9, page_size=8, max_len=64)
    assert eng._states is None and set(eng._pages[0]) == {"k", "v"}
    ticks = []
    eng.run([Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                     max_new_tokens=4)], tick_sink=ticks.append)
    new = {"index_rows_read", "index_rows_gathered", "sparse_blocks_selected",
           "state_slots_updated", "state_resets", "pages_held"}
    assert ticks and not any(new & set(t) for t in ticks)
    assert [len(np.asarray(eng._tick_counts))] == [1]


def test_the_groups_the_model_names(served):
    model = served[2]
    assert model.cache_groups() == ((0, (0,)),)
    assert model.state_layers() == (1, 2, 3)
    assert [model.rotary(i) for i in range(4)] == [False, True, True, True]
    eng = engine(served)
    assert len(eng._pages) == 1 and set(eng._pages[0]) == {"k", "v", "kc"}
    assert eng._pages[0]["kc"].shape == (97, 2, 1, 16)
    assert [s.shape for s in eng._states] == [(3, 4, 16, 16)] * 3
    assert all(s.dtype == jnp.float32 for s in eng._states)


# -- 7. refusals ---------------------------------------------------------------

def _prefix(served):
    engine(served).run(requests(served[1], lens=(9,), new=(3,)), prefix=True)


def _spill(served):
    build_scheduler(slots=2, num_pages=9, page_size=4, max_len=32,
                    prefix=True, host_pages=4, states=True)


def _speculation(served):
    engine(served, spec="lookup", spec_k=4)


def _fleet(served):
    from mpi_cuda_cnn_tpu.serve.core import EngineCompute
    from mpi_cuda_cnn_tpu.serve.fleet import Replica

    Replica("r0", EngineCompute(engine(served)), slots=3, num_pages=97,
            page_size=4, max_len=MAX_LEN)


def _adopt(served):
    engine(served).adopt_pages(engine(served), [1], [1])


def _detach(served):
    sched = build_scheduler(slots=2, num_pages=9, page_size=4, max_len=32,
                            states=True)
    sched.detach_for_handoff(sched.slots[0], "token")


def _spill_page(served):
    engine(served).spill_page(1)


def _copy_page(served):
    engine(served).copy_page(1, 2)


def _trainer_init(served):
    served[2].init(jax.random.key(0))


def _trainer_apply(served):
    served[2].apply(served[3], jnp.zeros((1, 4), jnp.int32))


def _int8_cache(served):
    engine(served, cache_dtype="int8")


@pytest.mark.parametrize("what,match", [
    (_prefix, "no state to start from|nobody kept it"),
    (_spill, "nobody kept it"), (_speculation, "cannot be rolled back"),
    (_fleet, "would stay behind"), (_adopt, "would stay behind"),
    (_detach, "would stay behind"), (_spill_page, "would stay behind"),
    (_copy_page, "would stay behind"),
    (_trainer_init, "linear layers"), (_trainer_apply, "linear layers"),
    (_int8_cache, "means of float K"),
])
def test_what_a_state_that_is_no_page_cannot_follow_refuses(served, what,
                                                           match):
    with pytest.raises(ValueError, match=match):
        what(served)


@pytest.mark.parametrize("kw,match", [
    (dict(depth=2, mixers=("attn",)), "want 2 of"),
    (dict(depth=1, mixers=("conv",)), "want 1 of"),
    (dict(depth=1, mixers=("linear",)), "linear None"),
    (dict(depth=1, linear=LinearAttn()), "needs `mixers`"),
    (dict(depth=1, select=SparseSelect(), pos="rope",
          layout=((True, True),), window=4), "without a sliding window"),
])
def test_a_description_that_says_nothing_is_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerLM(**kw)


@pytest.mark.parametrize("kw", [dict(kernel=3, stride=2), dict(block=7),
                                dict(kernel=128, stride=16, block=64),
                                dict(topk=0)])
def test_a_selection_of_no_whole_strides_is_refused(kw):
    with pytest.raises(ValueError, match="whole strides"):
        SparseSelect(**kw)


@pytest.mark.parametrize("over,match", [
    (dict(attn_use_rope=True), "attn_use_rope"),
    (dict(qk_norm=False), "qk_norm"),
    (dict(lightning_nkv=2), "lightning heads"),
    (dict(mixer_types=["minicpm4", "mamba", "minicpm4", "minicpm4"]),
     "mixer_types"),
    (dict(first_layer_held=2), "mixer_types"),
])
def test_the_family_refuses_what_the_program_cannot_be(over, match):
    with pytest.raises(ValueError, match=match):
        FAM.weights.dims(tiny_cfg(**over))


def test_the_published_depth_scales_the_residual_of_a_cut_stage():
    cut = FAM.weights.dims(tiny_cfg(
        num_hidden_layers=2, first_layer_held=1,
        published={"num_hidden_layers": 4}))
    assert cut["mixers"] == ("linear", "linear")
    assert cut["residual_scale"] == pytest.approx(1.4 / 2.0)
    assert FAM.weights.dims(tiny_cfg())["logit_scale"] == 0.5
