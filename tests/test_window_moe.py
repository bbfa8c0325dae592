"""Sliding-window and global layers in one model, a page pool and a block
table a layer group, a softmax top-k router that reads the layer's
input, ReGLU experts all held here (ISSUE 32), against the plain
reference of the benchmark's `window_moe` family, which is loaded by
its path and imports nothing of the program. Tiny widths (dim 64, 4
query / 2 K/V heads of 32, so heads x head_dim != dim; 8 layers [global,
window x 3] x 2; window 8, pages of 4, chunks of 4; 8 experts, top 3),
seeded weights, on the CPU.

The model:
1. Chunked prefill then decode through the two groups' pools, past
   three windows, give the reference's full-forward logits: f32 to 2e-4
   absolute on logits of unit scale (the two sum in other orders,
   nothing else differs); bf16 to 0.08 in the mean, which the reference
   one precision down (fp8) misses by more than twice. Read whole (as the
   code itself reads tables this small), by the loop, and by the loop
   with the running fold a 512-row chunk takes.
2. The window's edge: a key exactly `window` back is not seen, the one
   `window - 1` back is, in the contiguous cache and in both paged reads.
3. A layer without rotary is not rotated; one with is, at the stated
   theta.
4. The router read after attention, a SiLU gate, or weights left
   unnormalised each FAIL the comparison.
5. All experts held and no shared expert: the grouped products equal
   the dense sum over the chosen experts.
6. Paged = contiguous for a windowed model.

The groups:
7. A slot's windowed pages never exceed pages_for(window + chunk) + 1 at
   any depth, and cover every row its next forward reads or writes.
8. Freed pages return to the pool and are never read: every windowed
   page no slot holds is filled with a large value before every
   program, and the tokens do not move.
9. Admit / finish / preempt / squeeze storms leave check() true on
   both pools, every iteration, and both pools empty at the end.
10. `kv_rows_read_window`, `pages_held`, `window_pages_freed` by hand.
11. What cannot yet mean anything for a windowed group refuses, by
    mechanism: prefix sharing, spill, speculation, hand-off, the
    trainers' init / apply.
12. The counters stay on the device without a sink; a one-group model's
    record has none of the new fields.
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
from benchmarks.rounding import round_to  # noqa: E402
from mpi_cuda_cnn_tpu.models.generate import (  # noqa: E402
    attend_contiguous,
    decode_block,
    init_cache,
)
from mpi_cuda_cnn_tpu.models.transformer import TransformerLM  # noqa: E402
from mpi_cuda_cnn_tpu.parallel.ep import (  # noqa: E402
    moe_held_inference,
    route_softmax,
)
from mpi_cuda_cnn_tpu.serve import paged_cache  # noqa: E402
from mpi_cuda_cnn_tpu.serve.core import build_scheduler  # noqa: E402
from mpi_cuda_cnn_tpu.serve.engine import PagedEngine  # noqa: E402
from mpi_cuda_cnn_tpu.serve.paged_cache import (  # noqa: E402
    bounded_read,
    init_paged_cache,
    paged_forward,
    pages_for,
)
from mpi_cuda_cnn_tpu.serve.pool import WindowGroup  # noqa: E402
from mpi_cuda_cnn_tpu.serve.scheduler import Request, Slot  # noqa: E402
from mpi_cuda_cnn_tpu.faults import FaultInjector  # noqa: E402

BENCH = ROOT / "benchmarks"
TINY = BENCH / "tests" / "tiny_window"
SEED = 2**31 + 32
FAM = run.load_family(BENCH / "families" / "window_moe")
WINDOW, PAGE, CHUNK = 8, 4, 4
LOOP = (2, 3)       # blocks of 2 pages, 3 (slot, block) items a step


def tiny_cfg(**over):
    cfg = json.loads((TINY / "configs" / "tiny-window.json").read_text())
    return {**cfg, "sliding_window_size": WINDOW, "max_position_embeddings":
            64, "vocab_size": 96, "weights_dtype": "float32",
            "cache_dtype": "float32", **over}


def build(**over):
    cfg = tiny_cfg(**over)
    dm = FAM.weights.dims(cfg)
    return cfg, dm, FAM.build.model_of(dm), FAM.build.serving_params(
        dm, SEED, cfg)


@pytest.fixture(scope="module")
def served():
    return build()


def force_read(monkeypatch, read):
    """`whole`: the code's own choice at these sizes. `loop`: the
    bounded read's loop. `running`: the loop with the running fold that
    a slot with many query rows takes."""
    if read != "whole":
        monkeypatch.setattr(paged_cache, "read_step", lambda *a, **k: LOOP)
    if read == "running":
        # bounded_read is jitted by itself and keeps its traces by shape
        # and step: the fold's choice is made inside it, at trace time.
        monkeypatch.setattr(paged_cache, "_many_queries", lambda q: True)
        bounded_read.clear_cache()


def serve_sequence(model, params, seq, n_prompt, *, dtype=jnp.float32,
                   free_behind=False, poison=None):
    """Logits of every position of `seq`: its first `n_prompt` tokens
    by prefill chunks, the rest by decode ticks, in slot 1 of 2 (slot 0
    dead) through the model's two layer groups. `free_behind`: the
    windowed group's table forgets, before every program, the pages
    wholly behind the window, as the scheduler does; `poison` then
    fills the forgotten pages' rows."""
    caches = init_paged_cache(model, slots=2, num_pages=17, page_size=PAGE,
                              dtype=dtype, max_len=64)
    assert [(c.window, len(c.pages)) for c in caches] == [(0, 2), (WINDOW, 6)]
    got = {}
    program = jax.jit(functools.partial(paged_forward, model))

    def forward(toks, pos, valid, start):
        nonlocal caches
        tables = [np.zeros((2, 16), np.int32) for _ in caches]
        for t in tables:
            t[1] = np.arange(1, 17)
        if free_behind:
            gone = max(start - WINDOW + 1, 0) // PAGE
            tables[1][1, :gone] = 0
            if poison is not None and gone:
                caches = (caches[0], dataclasses.replace(
                    caches[1], pages=[
                        {n: a.at[1:1 + gone].set(poison) for n, a in c.items()}
                        for c in caches[1].pages]))
        caches = tuple(dataclasses.replace(c, block_table=jnp.asarray(t))
                       for c, t in zip(caches, tables))
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
        logits = forward(toks, pos, valid, start)
        got.update({start + j: logits[1, j] for j in range(n)})
    for p in range(n_prompt, len(seq)):
        logits = forward(np.array([[0], [seq[p]]], np.int32),
                         np.array([[0], [p]], np.int32),
                         np.array([[False], [True]]), p)
        got[p] = logits[1, 0]
    return np.stack([got[p] for p in range(len(seq))]), caches


# -- 1. the whole model against the reference ---------------------------------

N_PROMPT, N_TOTAL = 21, 40      # 40 positions: past three windows of 8


def sequence(dm):
    return np.random.default_rng(1).integers(0, dm["vocab"], N_TOTAL)


@pytest.mark.parametrize("read", ["whole", "loop", "running"])
def test_prefill_then_paged_decode_matches_the_reference(served, read,
                                                         monkeypatch):
    _, dm, model, params = served
    force_read(monkeypatch, read)
    seq = sequence(dm)
    got, caches = serve_sequence(model, params, seq, N_PROMPT,
                                 free_behind=True, poison=1e4)
    want = FAM.reference.forward_logits(
        dm, SEED, [seq], [np.arange(N_TOTAL)])[0][0]
    # f32 on both sides: the program sums its softmax block by block and
    # its experts pair by pair, the reference whole; 2e-4 on logits of
    # unit scale is that and nothing else.
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=0)
    assert float(jnp.std(want)) > 0.5
    if read == "running":
        bounded_read.clear_cache()
    counts = np.asarray(caches[0].counts).tolist()
    # One live row: 3 choices in each of 8 layers, every expert held.
    assert counts[:3] == [24, counts[1], 1] and counts[1] <= 24
    if read == "whole":         # every table row, every layer; 6 windowed
        assert counts[3:] == [8 * 2 * 64, 6 * 2 * 64]
    else:       # position 39: a global layer's 5 blocks of 8 keys and
        # the dead slot's 1, 6 items = 2 steps of 3; a windowed layer
        # starts at block 4 (39 - 7 = 32): 1 + 1 items, 1 step.
        assert counts[3:] == [2 * 2 * 24 + 6 * 24, 6 * 24]


def test_bf16_matches_the_reference_and_the_control_does_not():
    cfg, dm, model, params = build(weights_dtype="bfloat16",
                                   cache_dtype="bfloat16")
    seq = sequence(dm)
    got, _ = serve_sequence(model, params, seq, N_PROMPT,
                            dtype=jnp.bfloat16, free_behind=True)
    want, low = (x[0] for x in FAM.reference.forward_logits(
        dm, SEED, [seq], [np.arange(N_TOTAL)], (None, "fp8")))
    # bf16 weights and cache rows against the f32 reference; a near-tie
    # among the top-3 of 8 experts that bf16 flips moves a token's
    # routed part whole, so the largest error is an outlier and the
    # mean is what parts the program from the control one precision
    # down.
    err, control = np.abs(got - want), np.abs(np.asarray(low) - want)
    assert err.mean() < 0.08 and control.mean() > 2 * err.mean(), (
        err.mean(), control.mean())


# -- 2. the window's edge ----------------------------------------------------

def edge_weights(read, window, monkeypatch):
    """The softmax weight a query at position 12 puts on each of 13
    keys: keys all alike (equal scores), values one-hot markers, so the
    output reads the weights off."""
    q = jnp.ones((1, 1, 2, 16))
    k = jnp.ones((1, 13, 1, 16))
    v = jnp.eye(16)[None, :13, None, :]
    if read == "contiguous":
        c = {"k": jnp.zeros((1, 16, 1, 16)), "v": jnp.zeros((1, 16, 1, 16))}
        c = {"k": c["k"].at[:, :12].set(k[:, :12]),
             "v": c["v"].at[:, :12].set(v[:, :12])}
        o, _ = attend_contiguous(c, q, k[:, 12:], v[:, 12:], 12,
                                 jnp.asarray([12]), window)
    else:
        pool = {"k": jnp.zeros((5, 4, 1, 16)).at[1:5].set(
                    jnp.pad(k, ((0, 0), (0, 3), (0, 0), (0, 0))
                            ).reshape(4, 4, 1, 16)),
                "v": jnp.zeros((5, 4, 1, 16)).at[1:5].set(
                    jnp.pad(v, ((0, 0), (0, 3), (0, 0), (0, 0))
                            ).reshape(4, 4, 1, 16))}
        o, _ = bounded_read(
            q, pool, jnp.asarray([[12]]), jnp.asarray([[True]]),
            jnp.asarray([[1, 2, 3, 4]], jnp.int32), page_size=4,
            step=(4, 1) if read == "whole" else (1, 1), window=window)
    return np.asarray(o).reshape(2, 16)[0, :13]


@pytest.mark.parametrize("read", ["contiguous", "whole", "loop"])
def test_the_windows_edge(read, monkeypatch):
    w = edge_weights(read, 5, monkeypatch)
    # Window 5 at position 12: keys 8..12, the query itself included.
    np.testing.assert_allclose(w[8:], 0.2, atol=1e-6)
    assert w[7] == 0 and not w[:8].any()      # exactly `window` back: unseen
    assert w[8] > 0                           # `window - 1` back: seen
    np.testing.assert_allclose(edge_weights(read, 0, monkeypatch), 1 / 13,
                               atol=1e-6)


# -- 3. rotary, layer by layer ------------------------------------------------

def test_a_layer_without_rotary_is_not_rotated_and_one_with_is(served):
    _, dm, model, params = served
    assert model.layout[0] == (False, False) and model.layout[1] == (True, True)
    y = jax.random.normal(jax.random.key(3), (1, 5, dm["width"]))
    pos = jnp.asarray([3, 17, 40, 41, 63])
    hd, half = dm["head_dim"], dm["head_dim"] // 2
    for layer, rotated in ((0, False), (1, True)):
        blk = params["blocks"][layer]
        q, k, v = model.project_qkv(blk, y, positions=pos, layer=layer)
        assert q.shape == (1, 5, 4, hd) and k.shape == v.shape == (1, 5, 2, hd)
        plain = (y @ blk["wq"]).reshape(1, 5, 4, hd)
        if not rotated:
            np.testing.assert_allclose(q, plain, atol=1e-6)
            continue
        # By hand, in numpy: pair i is entries i and i + half, turning
        # at theta ** (-i / half) a position.
        angle = (np.asarray(pos, np.float64)[:, None]
                 * 1.5e6 ** (-np.arange(half) / half))[None, :, None, :]
        a, b = np.asarray(plain[..., :half]), np.asarray(plain[..., half:])
        want = np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                               a * np.sin(angle) + b * np.cos(angle)], -1)
        np.testing.assert_allclose(q, want, atol=2e-5)
        other = dataclasses.replace(model, rope_theta=10000.0).project_qkv(
            blk, y, positions=pos, layer=layer)[0]
        assert float(jnp.max(jnp.abs(other - q))) > 0.1     # theta is read


# -- 4. what the comparison catches ------------------------------------------

def _after_attention(model, _):
    return dataclasses.replace(model, experts=dataclasses.replace(
        model.experts, reads="block")), None


def _silu_gate(model, _):
    return dataclasses.replace(model, experts=dataclasses.replace(
        model.experts, act="silu")), None


def _unnormalised(model, dm):
    def route(dm, x, router):       # the reference, its weights left raw
        p = jax.nn.softmax(x @ router["gate"], axis=-1)
        cut = jnp.sort(p, axis=-1)[:, -dm["top_k"]][:, None]
        return jnp.where(p >= cut, p, 0.0)

    return model, route


@pytest.mark.parametrize("fault", [_after_attention, _silu_gate,
                                   _unnormalised])
def test_a_wrong_block_fails_the_comparison(served, fault, monkeypatch):
    _, dm, model, params = served
    model, route = fault(model, dm)
    if route is not None:
        monkeypatch.setattr(FAM.reference, "route", route)
        FAM.reference._jitted.cache_clear()
    seq = sequence(dm)
    got, _ = serve_sequence(model, params, seq, N_PROMPT)
    want = FAM.reference.forward_logits(
        dm, SEED, [seq], [np.arange(N_TOTAL)])[0][0]
    if route is not None:
        monkeypatch.undo()
        FAM.reference._jitted.cache_clear()
    assert float(np.max(np.abs(got - want))) > 100 * 2e-4


# -- 5. the expert layer ------------------------------------------------------

def test_all_held_and_no_shared_expert_is_the_dense_sum(served):
    _, dm, model, params = served
    blk, spec = params["blocks"][1], model.experts
    assert "shared" not in blk and spec.held == tuple(range(8))
    x = jax.random.normal(jax.random.key(5), (24, dm["width"]))
    ids, w = route_softmax(x, blk["router"], spec)
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 1.0, atol=1e-6)
    p = jax.nn.softmax(x @ blk["router"]["gate"], axis=-1)
    assert np.array_equal(np.sort(np.asarray(ids), -1),
                          np.sort(np.argsort(-np.asarray(p), -1)[:, :3], -1))
    out, counts = moe_held_inference(x, blk, spec)
    assert np.asarray(counts).tolist()[0] == 24 * 3
    bank = blk["experts"]
    with jax.default_matmul_precision("highest"):
        want = sum(
            jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1, keepdims=True)
            * FAM.reference.reglu(x, bank["wg"][e], bank["wu"][e],
                                  bank["wd"][e])
            for e in range(8))
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=1e-5)
    # The choice handed in from elsewhere is the one used.
    ids2 = (ids + 1) % 8
    out2, _ = moe_held_inference(x, blk, spec, routing=(ids2, w))
    assert float(jnp.max(jnp.abs(out2 - out))) > 1e-2


# -- 6. paged = contiguous ----------------------------------------------------

def test_paged_equals_contiguous_for_a_windowed_model(served):
    _, dm, model, params = served
    seq = sequence(dm)
    paged, _ = serve_sequence(model, params, seq, N_PROMPT)
    assert init_cache(model, 1)[0]["k"].shape == (1, 64, 2, 32)
    step = jax.jit(functools.partial(decode_block, model))
    cache, got = init_cache(model, 1), []
    for start in range(0, N_PROMPT, CHUNK):
        n = min(CHUNK, N_PROMPT - start)
        logits, cache = step(params, jnp.asarray(seq[None, start:start + n]),
                             jnp.int32(start), cache)
        got.extend(logits[0])
    for p in range(N_PROMPT, N_TOTAL):
        logits, cache = step(params, jnp.asarray(seq[None, p:p + 1]),
                             jnp.int32(p), cache)
        got.append(logits[0, 0])
    np.testing.assert_allclose(np.stack(got), paged, atol=1e-5, rtol=0)


# -- 7. the windowed group's bound -------------------------------------------

@pytest.mark.parametrize("window,chunk,page", [(8, 4, 4), (32, 16, 16),
                                               (10, 7, 4), (5, 16, 8)])
def test_a_slots_windowed_pages_stay_under_the_bound(window, chunk, page):
    group = WindowGroup(window=window, chunk=chunk, page_size=page, slots=1,
                        max_len=400)
    bound = pages_for(window + chunk, page) + 1
    assert group.per_slot == bound and group.pool.usable == bound
    slot = Slot(0, req=Request(rid=7, prompt=np.zeros(150, np.int32),
                               max_new_tokens=200), target=150)
    most = 0
    while slot.cached < 350:
        rows = min(chunk, slot.target - slot.cached) if slot.prefilling else 1
        group.advance(slot, rows)
        held = [b for b, p in enumerate(slot.wpages) if p]
        # Every row the forward reads or writes has its page...
        first = max(slot.cached - window + 1, 0) // page
        last = (slot.cached + rows - 1) // page
        assert held == list(range(first, last + 1)), (slot.cached, held)
        # ... nothing else does, and the bound holds at every depth.
        assert len(held) <= bound
        most = max(most, len(held))
        group.check([slot])
        slot.cached += rows
    assert most >= pages_for(window, page)
    freed = group.drain_freed()
    assert freed == len(slot.wpages) - len([p for p in slot.wpages if p])
    group.release(slot)
    assert group.pool.free_pages == group.pool.usable


# -- the engine over both groups ---------------------------------------------

def engine(served, **kw):
    _, _, model, params = served
    kw = {"slots": 3, "num_pages": 3 * 16 + 1, **kw}
    return PagedEngine(model, params, page_size=PAGE, prefill_chunk=CHUNK,
                       cache_dtype="float32", max_len=64, **kw)


def requests(dm, lens=(9, 30, 17, 5, 23), new=(30, 20, 8, 40, 12)):
    rng = np.random.default_rng(9)
    return [Request(rid=i, prompt=rng.integers(0, dm["vocab"], n).astype(
        np.int32), max_new_tokens=m) for i, (n, m) in enumerate(zip(lens, new))]


def outputs(res):
    return {r.rid: r.out for r in res.requests}


def test_the_engine_serves_the_references_greedy_tokens(served):
    _, dm, _, _ = served
    res = engine(served).run(requests(dm))
    assert res.status_counts() == {"finished": 5}
    for r in res.requests:
        seq = np.concatenate([r.prompt, np.asarray(r.out, np.int32)])
        rows = np.arange(r.prompt.size - 1, seq.size - 1)
        want = FAM.reference.forward_logits(dm, SEED, [seq], [rows])[0][0]
        gap = np.max(want, -1) - np.take_along_axis(
            np.asarray(want), np.asarray(r.out)[:, None], -1)[:, 0]
        assert float(gap.max()) < 1e-3      # the reference's own choices


# -- 8. freed pages are never read -------------------------------------------

def test_freed_pages_return_to_the_pool_and_are_never_read(served):
    _, dm, _, _ = served
    want = outputs(engine(served).run(requests(dm)))
    eng = engine(served)
    seen = {"poisoned": 0, "sched": None}
    real_step, real_tables = WindowGroup.advance, PagedEngine._tables

    def advance(group, slot, rows):
        seen["group"] = group
        return real_step(group, slot, rows)

    def tables(self, x, slots):
        # Before every program: every windowed page that no slot holds
        # (the freed ones among them) is filled with a large value.
        group = seen.get("group")
        if group is not None:
            free = sorted(group.pool._free)
            seen["poisoned"] += len(free)
            idx = jnp.asarray(free, jnp.int32)
            self._pages = (self._pages[0], [
                {n: a.at[idx].set(1e4) for n, a in c.items()}
                for c in self._pages[1]])
        return real_tables(self, x, slots)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(WindowGroup, "advance", advance)
        mp.setattr(PagedEngine, "_tables", tables)
        ticks = []
        res = eng.run(requests(dm), tick_sink=ticks.append)
    assert outputs(res) == want
    assert seen["poisoned"] > 0
    assert sum(t["window_pages_freed"] for t in ticks) > 10
    # Given back means issued again: far more pages were taken over the
    # run than the windowed pool has.
    group = seen["group"]
    assert group.pool.free_pages == group.pool.usable == 3 * 4


# -- 9. storms ----------------------------------------------------------------

@pytest.mark.parametrize("storm", ["preempt", "squeeze", "expire", "static"])
def test_storms_leave_both_pools_whole(served, storm):
    _, dm, _, _ = served
    reqs = requests(dm, lens=(9, 30, 17, 5, 23, 12, 28),
                    new=(30, 20, 8, 30, 12, 25, 6))
    kw, run_kw = {}, {}
    if storm == "preempt":          # a global pool too small for three
        kw["num_pages"] = 20
    elif storm == "squeeze":
        run_kw["faults"] = FaultInjector(
            "squeeze@serve.tick:3?pages=30&ticks=6;"
            "squeeze@serve.tick:25?pages=40&ticks=4")
    elif storm == "expire":
        for r in reqs[1::2]:
            r.deadline = 0.0 + 1e-3 * (r.rid + 1)
    elif storm == "static":
        run_kw["mode"] = "static"
    checked = []
    real = WindowGroup.check_changed

    def check_changed(group):
        checked.append(group.pool.free_pages)
        return real(group)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(WindowGroup, "check_changed", check_changed)
        res = engine(served, **kw).run(reqs, **run_kw)
    assert len(res.requests) == 7
    assert len(checked) > 20 and len(set(checked)) > 3      # every iteration
    if storm == "preempt":
        assert res.preemptions > 0
        assert res.status_counts() == {"finished": 7}
        plain = outputs(engine(served).run(requests(
            dm, lens=(9, 30, 17, 5, 23, 12, 28), new=(30, 20, 8, 30, 12, 25, 6))))
        assert outputs(res) == plain
    elif storm == "expire":
        assert res.status_counts().get("expired", 0) > 0
    else:
        assert res.status_counts() == {"finished": 7}


# -- 10. the counters by hand -------------------------------------------------

def test_the_tick_record_counts_both_groups(served, monkeypatch):
    _, dm, _, _ = served
    monkeypatch.setattr(paged_cache, "read_step", lambda *a, **k: LOOP)
    ticks = []
    # One request: 10 prompt tokens (3 chunks), 20 new; alone in 3 slots.
    res = engine(served).run(requests(dm, lens=(10,), new=(20,)),
                             tick_sink=ticks.append)
    assert res.status_counts() == {"finished": 1}
    decoded = [t for t in ticks if t["decoded"]]
    assert len(decoded) == 19       # the first token came with the prefill
    for i, t in enumerate(decoded):
        p = 10 + i                  # the position this tick wrote
        # Blocks of 8 keys. A global layer: the live slot's p // 8 + 1
        # blocks and one a dead slot, in steps of 3 items; a windowed
        # one: from the block of p - 7 on.
        g = -(-(p // 8 + 1 + 2) // 3) * 3 * 8
        w = -(-(p // 8 - max(p - 7, 0) // 8 + 1 + 2) // 3) * 3 * 8
        assert t["kv_rows_read"] == 2 * g + 6 * w
        assert t["kv_rows_read_window"] == 6 * w
        # Pages at the iteration's end (the row at p written): the
        # global group keeps them all, the windowed one those from
        # (p - 7) // 4 on -- given back at the NEXT advance, so the
        # tick that wrote p still holds what p itself needed.
        last = t is decoded[-1]
        assert t["pages_held"] == ([0, 0] if last else [
            pages_for(p + 1, 4), p // 4 - max(p - 7, 0) // 4 + 1])
    freed = [t["window_pages_freed"] for t in ticks]
    # Page b (rows 4b..4b+3) goes when cached - 7 >= 4b + 4; the run
    # ends at cached 29: pages 0..4 were given back behind the window.
    assert sum(freed) == 5 and max(freed) == 1
    assert not any("kv_rows_read_window" in t for t in ticks
                   if not t["decoded"])


# -- 11. refusals, by mechanism ----------------------------------------------

def _prefix(served):
    engine(served).run(requests(served[1], lens=(9,), new=(3,)), prefix=True)


def _spill(served):
    build_scheduler(slots=2, num_pages=9, page_size=4, max_len=32,
                    prefix=True, host_pages=4, window=(8, 4))


def _speculation(served):
    engine(served, spec="lookup", spec_k=4)


def _handoff(served):
    from mpi_cuda_cnn_tpu.serve.core import EngineCompute
    from mpi_cuda_cnn_tpu.serve.fleet import Replica

    Replica("r0", EngineCompute(engine(served)), slots=3, num_pages=49,
            page_size=4, max_len=64)


def _adopt(served):
    engine(served).adopt_pages(engine(served), [1], [1])


def _detach(served):
    sched = build_scheduler(slots=2, num_pages=9, page_size=4, max_len=32,
                            window=(8, 4))
    sched.detach_for_handoff(sched.slots[0], "token")


def _trainer_init(served):
    served[2].init(jax.random.key(0))


def _trainer_apply(served):
    served[2].apply(served[3], jnp.zeros((1, 4), jnp.int32))


@pytest.mark.parametrize("what,match", [
    (_prefix, "prefix sharing"), (_spill, "spill"),
    (_speculation, "speculation"), (_handoff, "hand-off"),
    (_adopt, "hand-off"), (_detach, "hand-off"),
    (_trainer_init, "per-layer layout"), (_trainer_apply, "per-layer layout"),
])
def test_what_means_nothing_for_a_windowed_group_refuses(served, what, match):
    with pytest.raises(ValueError, match=match):
        what(served)


@pytest.mark.parametrize("kw,match", [
    (dict(window=4), "needs a layout"),
    (dict(pos="rope", depth=2, layout=((True, True),), window=4), "layout of 1"),
    (dict(pos="rope", depth=1, layout=((True, True),)), "window 0"),
    (dict(pos="learned", depth=1, layout=((False, False),)), "pos='rope'"),
])
def test_a_layout_that_says_nothing_is_refused(kw, match):
    with pytest.raises(ValueError, match=match):
        TransformerLM(**kw)


def test_an_engine_needs_a_global_group(served):
    _, dm, _, params = served
    every = dataclasses.replace(
        served[2], layout=tuple((True, True) for _ in range(8)))
    assert every.cache_groups() == ((WINDOW, tuple(range(8))),)
    with pytest.raises(ValueError, match="global group"):
        PagedEngine(every, params, page_size=PAGE, max_len=64)


# -- 12. the counters stay where they are ------------------------------------

def test_counts_are_fetched_by_a_sink_and_by_nothing_else(served):
    class NotForTheHost:
        def __array__(self, *a, **kw):
            raise AssertionError("the tick's counts were fetched")

    eng = engine(served)
    tick = eng._tick

    def counted(*args):
        caches, nxt = tick(*args)
        return (dataclasses.replace(caches[0], counts=NotForTheHost()),
                caches[1]), nxt

    counted._cache_size = tick._cache_size
    eng._tick = counted
    dm = served[1]
    res = eng.run(requests(dm, lens=(5, 9), new=(4, 6)))
    assert res.status_counts() == {"finished": 2}
    with pytest.raises(AssertionError, match="counts were fetched"):
        eng.run(requests(dm, lens=(5,), new=(4,)), tick_sink=lambda t: None)


def test_a_chunks_counts_are_on_its_record_and_fetched_by_a_sink_alone(served):
    """The prefill chunk's program returns the expert layers' counts
    too: with a sink its pairs and experts hit are on the record of
    every iteration that ran a chunk, and of no other; with none they
    stay on the device."""
    class NotForTheHost:
        def __array__(self, *a, **kw):
            raise AssertionError("the chunk's counts were fetched")

    _, dm, model, _ = served
    eng = engine(served)
    ticks = []
    res = eng.run(requests(dm, lens=(10, 5), new=(6, 4)),
                  tick_sink=ticks.append)
    assert res.status_counts() == {"finished": 2}
    chunks = [t for t in ticks if t["prefill"] is not None]
    assert len(chunks) == 3 + 2 and len(chunks) < len(ticks)
    names = {"chunk_moe_assignments", "chunk_moe_experts_hit"}
    assert all(names <= set(t) for t in chunks)
    assert not any(names & set(t) for t in ticks if t["prefill"] is None)
    layers, spec = model.depth, model.experts
    for t in chunks:
        # All experts are held: every valid row's every choice lands.
        assert t["chunk_moe_assignments"] == (
            t["prefill"][2] * spec.top_k * layers)
        assert 0 < t["chunk_moe_experts_hit"] <= min(
            t["chunk_moe_assignments"], len(spec.held) * layers)
    prefill = eng._prefill

    def counted(*args):
        caches, nxt = prefill(*args)
        return (dataclasses.replace(caches[0], counts=NotForTheHost()),
                caches[1]), nxt

    counted._cache_size = prefill._cache_size
    eng._prefill = counted
    res = eng.run(requests(dm, lens=(5, 9), new=(4, 6)))
    assert res.status_counts() == {"finished": 2}
    with pytest.raises(AssertionError, match="chunk's counts were fetched"):
        eng.run(requests(dm, lens=(5,), new=(4,)), tick_sink=lambda t: None)


def test_a_one_group_model_has_none_of_the_new_fields():
    model = TransformerLM(vocab=64, dim=32, heads=4, kv_heads=2, depth=2,
                          max_seq=64, pos="rope")
    assert model.cache_groups() == ((0, (0, 1)),)
    assert model.head_dim == 8 and model.rotary(0) and not model.layer_window(1)
    eng = PagedEngine(model, model.init(jax.random.key(0)), slots=2,
                      num_pages=9, page_size=8, max_len=64)
    assert isinstance(eng._pages, list) and eng._window is None
    ticks = []
    eng.run([Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                     max_new_tokens=4)], tick_sink=ticks.append)
    new = {"kv_rows_read_window", "pages_held", "window_pages_freed",
           "chunk_moe_assignments", "chunk_moe_experts_hit"}
    assert ticks and not any(new & set(t) for t in ticks)


def test_the_read_steps_the_code_picks_at_the_published_widths():
    """28 query / 4 K/V heads of 128, bf16 rows (2,048 B a key), 1,024
    table pages of 16: a tick's read takes 512-key blocks, 8 a step, as
    every K/V tick does; a 512-row chunk weighs a key's operations and
    its 7.3 MB carry too and takes 512-key blocks, one a step (128-key
    blocks without the carry: PERF.md section 6, PR 32). The latent
    read's pick (PR 31) is what it was."""
    read_step = paged_cache.read_step
    assert read_step(32, 1024, 16, 2048) == (32, 8)
    many = 4 * 28 * 512 * 128
    q = jnp.zeros((1, 512, 28, 128))
    assert paged_cache._many_queries(q)
    assert not paged_cache._many_queries(q[:, :32])
    assert read_step(1, 1024, 16, 2048, key_flops=many, stat_bytes=many,
                     carry_bytes=many) == (32, 1)
    assert read_step(1, 1024, 16, 2048, key_flops=many,
                     stat_bytes=many) == (8, 1)
    assert read_step(64, 128, 16, 1280, key_flops=2 * 128 * (640 + 512),
                     stat_bytes=128 * 512 * 4) == (27, 7)


def test_the_family_refuses_what_the_program_cannot_be():
    with pytest.raises(ValueError, match="norm_topk_prob"):
        FAM.weights.dims(tiny_cfg(norm_topk_prob=False))
    with pytest.raises(ValueError, match="rope_layout"):
        FAM.weights.dims(tiny_cfg(rope_layout=[0, 1, 1]))
    FAM.work.check()
    dm = FAM.weights.dims(tiny_cfg())
    assert not hasattr(FAM.work, "tick_least_seconds")
    assert FAM.work.held_slots(dm) == 64
    assert round_to(jnp.ones((2, 2)), "fp8", 0).shape == (2, 2)
