"""Latent attention and a held share of routed experts, served through
the paged engine (ISSUE 28), against the plain reference of the
benchmark's `mla_moe` family, which is loaded by its path and imports
nothing of the program. Tiny widths, seeded weights, f32, on the CPU.

1. Prefill chunks then decode ticks through the latent page pool give
   the reference's full-forward logits (f32: 2e-4 absolute on logits of
   unit scale — the two sum in other orders, nothing else differs).
2. The absorbed and the materialized read of the latent rows agree.
3. The shares add up: 16 routed experts in 4 groups, 4 shares of 4 —
   the routed parts of all shares plus the shared expert ONCE are the
   uncut layer.
4. The router's bias chooses and does not weigh.
5. A token none of whose experts are held gets the shared expert only.
6. The K/V-format features run on latent rows (prefix sharing, copy-on-
   write, speculation's rollback, the host tier's spill and readmit,
   the prefill -> decode handoff between engines, obs/replay's mirror)
   and give the plain run's tokens; what cannot hold latent rows
   refuses at construction.
7. The GPT-2 family's golden numbers (benchmarks/testdata) stand.
8. The family's tiny benchmark (benchmarks/tests/tiny_mla): the program
   correct, the fp8 control not correct.
9. The bounded latent read (PR 31), its loop forced on at these tiny
   tables (`loop`): 1 and 2 again through it, `latent_rows_read` by
   hand, one tick and one prefill program for every depth. Tables this
   small are read WHOLE by the code's own choice (paged_cache.read_step),
   which is what every other test here runs.
"""

import dataclasses
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
from mpi_cuda_cnn_tpu.models.generate import attend_latent  # noqa: E402
from mpi_cuda_cnn_tpu.models.transformer import swiglu  # noqa: E402
from mpi_cuda_cnn_tpu.parallel.ep import (  # noqa: E402
    moe_held_inference,
    route_grouped,
)
from mpi_cuda_cnn_tpu.serve import paged_cache  # noqa: E402
from mpi_cuda_cnn_tpu.serve.engine import TICK_COUNTS, PagedEngine  # noqa: E402
from mpi_cuda_cnn_tpu.serve.paged_cache import (  # noqa: E402
    bounded_read_latent,
    init_paged_cache,
    paged_forward,
)
from mpi_cuda_cnn_tpu.serve.scheduler import Request  # noqa: E402

BENCH = ROOT / "benchmarks"
TINY = BENCH / "tests" / "tiny_mla"
SEED = 2**31 + 28
FAM = run.load_family(BENCH / "families" / "mla_moe")


def tiny_cfg(**over):
    cfg = json.loads((TINY / "configs" / "tiny-mla.json").read_text())
    return {**cfg, "weights_dtype": "float32", "cache_dtype": "float32",
            **over}


@pytest.fixture(scope="module")
def served():
    cfg = tiny_cfg()
    dm = FAM.weights.dims(cfg)
    return cfg, dm, FAM.build.model_of(dm), FAM.build.serving_params(
        dm, SEED, cfg)


# The bounded latent read's loop at tiny sizes: blocks of 2 pages, 3
# (slot, block) items a step (read_step itself reads these tables whole).
LOOP = (2, 3)


@pytest.fixture
def loop(monkeypatch):
    """Every latent read traced in the test takes the loop."""
    monkeypatch.setattr(paged_cache, "read_step", lambda *a, **k: LOOP)


def loop_rows(depths, page):
    """Rows one layer's loop touches for live slots at `depths` (a dead
    slot has no item): steps taken x rows a step."""
    width = LOOP[0] * page
    items = sum(d // width + 1 for d in depths)
    return -(-items // LOOP[1]) * LOOP[1] * width


@pytest.mark.parametrize("read", ["whole", "loop"])
def test_prefill_then_paged_decode_matches_the_reference(served, read,
                                                         monkeypatch):
    _, dm, model, params = served
    if read == "loop":
        monkeypatch.setattr(paged_cache, "read_step", lambda *a, **k: LOOP)
    page, chunk, n_prompt, n_total = 8, 16, 37, 49
    seq = np.random.default_rng(1).integers(0, dm["vocab"], n_total)
    cache = init_paged_cache(model, slots=2, num_pages=17, page_size=page,
                             max_len=64)
    # One pool a layer, one row a token: 40 values here (576 at full
    # size) and zero lanes up to the lane tile; no V pool.
    assert set(cache.pages[0]) == {"c"} and cache.pages[0]["c"].shape == (
        17, page, 128)
    assert model.attn.row == dm["kv_rank"] + dm["rope"] == 40
    # Slot 1 serves the sequence from pages 1..8; slot 0 stays dead.
    table = np.zeros((2, 8), np.int32)
    table[1] = np.arange(1, 9)
    cache = dataclasses.replace(cache, block_table=jnp.asarray(table))
    got = {}
    for start in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - start)
        toks = np.zeros((2, chunk), np.int32)
        toks[1, :n] = seq[start:start + n]
        pos = start + np.arange(chunk)[None, :] * np.ones((2, 1), np.int32)
        valid = np.zeros((2, chunk), bool)
        valid[1, :n] = True
        logits, cache = paged_forward(model, params, jnp.asarray(toks),
                                      jnp.asarray(pos), jnp.asarray(valid),
                                      cache)
        got.update({start + j: logits[1, j] for j in range(n)})
    for p in range(n_prompt, n_total):
        toks = np.array([[0], [seq[p]]], np.int32)
        logits, cache = paged_forward(
            model, params, jnp.asarray(toks),
            jnp.asarray([[0], [p]], jnp.int32),
            jnp.asarray([[False], [True]]), cache)
        got[p] = logits[1, 0]
        # One live row: its 4 choices among 16 experts, of which 4 are
        # held, in 2 expert layers; the read touched every table row
        # where the table is read whole (as the code itself reads one
        # this small), the live slot's blocks where the loop is forced.
        counts = dict(zip(TICK_COUNTS, np.asarray(cache.counts).tolist()))
        assert 0 <= counts["moe_assignments"] <= 2 * dm["top_k"]
        assert counts["moe_experts_hit"] <= counts["moe_assignments"]
        assert counts["latent_rows_read"] == dm["layers"] * (
            2 * 8 * page if read == "whole" else loop_rows([p], page))
    pool = np.asarray(cache.pages[0]["c"])
    assert np.all(pool[..., 40:] == 0) and np.any(pool[1:8, :, :40] != 0)
    rows = np.arange(n_total)
    want = FAM.reference.forward_logits(dm, SEED, [seq], [rows])[0][0]
    np.testing.assert_allclose(
        np.stack([got[p] for p in rows]), want, atol=2e-4, rtol=0)
    assert float(jnp.std(want)) > 0.5       # logits of unit scale


def materialized_read(q, rows, mask, wuk, wuv, a):
    """The other form of attend_latent's read, written out plainly:
    every row up-projected to per-head keys and values, then ordinary
    masked attention (what the family's reference computes, and what
    the program leaves until a slot has ~150 queries at once)."""
    c, kr = rows[..., :a.kv_rank], rows[..., a.kv_rank:a.row]
    kn = jnp.einsum("bkr,hnr->bkhn", c, wuk)
    v = jnp.einsum("bkr,hrv->bkhv", c, wuv)
    logits = (jnp.einsum("bqhn,bkhn->bhqk", q[..., :a.nope], kn)
              + jnp.einsum("bqhd,bkd->bhqk", q[..., a.nope:], kr))
    logits = jnp.where(mask[None, None], logits * a.softmax_scale, -jnp.inf)
    o = jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(logits, axis=-1), v)
    return o.reshape(*q.shape[:2], -1)


@pytest.mark.parametrize("form", ["whole", "bounded"])
@pytest.mark.parametrize("kk", [1, 5])
def test_absorbed_read_equals_materialized(served, kk, form):
    """`whole`: attend_latent over the rows. `bounded`: the same rows as
    two slots' pages of a pool (4 rows a page, tables in another order
    than the pool's), read by the bounded loop: still the materialized
    reference's numbers."""
    _, dm, model, _ = served
    a, h = model.attn, dm["heads"]
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (2, kk, h, a.nope + a.rope))
    # Stored rows: the row's values and the pool's zero lanes after them.
    rows = jnp.pad(jax.random.normal(ks[1], (2, 24, a.row)),
                   ((0, 0), (0, 0), (0, 8)))
    wuk = jax.random.normal(ks[2], (h, a.nope, a.kv_rank)) / 6
    wuv = jax.random.normal(ks[3], (h, a.kv_rank, a.v)) / 6
    mask = jnp.arange(24)[None, :] <= (24 - kk + jnp.arange(kk))[:, None]
    with jax.default_matmul_precision("highest"):
        if form == "whole":
            got = attend_latent(q, rows, mask, wuk, wuv, a)
        else:
            table = 1 + np.random.default_rng(4).permutation(12).reshape(2, 6)
            pool = jnp.zeros((13, 4, rows.shape[-1])).at[table].set(
                rows.reshape(2, 6, 4, -1))
            positions = jnp.broadcast_to(24 - kk + jnp.arange(kk), (2, kk))
            got, n = bounded_read_latent(
                q, pool, positions, jnp.ones((2, kk), bool),
                jnp.asarray(table, jnp.int32), wuk, wuv, page_size=4,
                step=LOOP, attn=a)
            assert int(n) == loop_rows([23, 23], 4) < 2 * 24 + 8
        want = materialized_read(q, rows, mask, wuk, wuv, a)
    assert got.shape == (2, kk, h * a.v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_the_router_stays_f32_under_bf16_weights():
    """The family's build keeps a layer's router out of the bf16 cast
    (a choice among near-equal scores is not a matmul to round); every
    other matrix is served in the configuration's type."""
    cfg = tiny_cfg(weights_dtype="bfloat16")
    dm = FAM.weights.dims(cfg)
    blocks = FAM.build.serving_params(dm, SEED, cfg)["blocks"]
    routed = [b for b in blocks if "router" in b]
    assert len(routed) == dm["layers"] - dm["dense_layers"] > 0
    for blk in routed:
        rest = {k: v for k, v in blk.items() if k != "router"}
        assert {a.dtype for a in jax.tree.leaves(blk["router"])} == {
            jnp.dtype("float32")}
        assert {a.dtype for a in jax.tree.leaves(rest)} == {
            jnp.dtype("bfloat16")}


def expert_layer(dm, first, held, seed=SEED, layer=1):
    """The program's tree of layer `layer`'s expert part for the share
    that holds ids first..first+held-1, and its routing spec."""
    share = {**dm, "first_held": first, "held": held}
    key = FAM.weights.root_key(seed)
    blk = FAM.weights.block_f32(share, key, layer, False)
    return ({k: blk[k] for k in ("router", "shared", "experts")},
            FAM.build.model_of(share).experts)


def test_the_shares_add_up_to_the_uncut_layer(served):
    _, dm, _, _ = served
    assert (dm["routed"], dm["groups"], dm["held"]) == (16, 4, 4)
    y = jax.random.normal(jax.random.key(5), (40, dm["width"]))
    shared = None
    routed = jnp.zeros_like(y)
    pairs = 0
    for first in range(0, 16, 4):
        blk, spec = expert_layer(dm, first, 4)
        out, counts = moe_held_inference(y, blk, spec)
        shared = swiglu(y, blk["shared"])
        routed = routed + (out - shared)
        pairs += int(counts[0])
    assert pairs == 40 * dm["top_k"]            # every choice, once
    # The uncut layer by the reference: every expert, weighted.
    blk, _ = expert_layer(dm, 0, 16)
    with jax.default_matmul_precision("highest"):
        w = FAM.reference.route(dm, y, blk["router"])
        want = FAM.reference._swiglu(y, blk["shared"])
        for e in range(16):
            want = want + w[:, e:e + 1] * FAM.reference._swiglu(
                y, {m: blk["experts"][m][e] for m in ("wg", "wu", "wd")})
    assert int((np.asarray(w) > 0).sum()) == 40 * dm["top_k"]
    np.testing.assert_allclose(routed + shared, want, atol=2e-5, rtol=1e-5)


def test_the_bias_chooses_and_does_not_weigh(served):
    _, dm, _, _ = served
    blk, spec = expert_layer(dm, 0, 4)
    y = jax.random.normal(jax.random.key(6), (32, dm["width"]))
    router = blk["router"]
    s = jax.nn.sigmoid(y @ router["gate"])
    # A bias that lifts expert 9 (and with it its group) over all others.
    lifted = {**router, "bias": router["bias"].at[9].set(3.0)}
    ids0, _ = route_grouped(y, router, spec)
    ids, w = route_grouped(y, lifted, spec)
    assert bool(jnp.all(jnp.any(ids == 9, axis=-1)))
    assert not bool(jnp.all(jnp.any(ids0 == 9, axis=-1)))
    # ... and the weights are the chosen experts' s alone, bias-free.
    chosen = jnp.take_along_axis(s, ids, axis=-1)
    np.testing.assert_allclose(
        w, chosen / chosen.sum(-1, keepdims=True) * spec.scale, rtol=1e-6)
    assert float(jnp.max(w)) < spec.scale       # no 3.0 leaked in
    # The groups: every choice lies in the 2 best of 4 groups.
    assert all(len(set(row // 4)) <= spec.top_groups
               for row in np.asarray(ids))


def test_a_token_with_no_held_expert_gets_the_shared_expert_only(served):
    _, dm, _, _ = served
    blk, spec = expert_layer(dm, 4, 4)          # holds ids 4..7
    y = jax.random.normal(jax.random.key(7), (24, dm["width"]))
    # A bias that keeps every token away from the held group.
    away = {**blk, "router": {**blk["router"], "bias": blk["router"][
        "bias"].at[jnp.arange(4, 8)].set(-5.0)}}
    out, counts = moe_held_inference(y, away, spec)
    np.testing.assert_array_equal(counts, [0, 0, 0])
    np.testing.assert_array_equal(out, swiglu(y, blk["shared"]))
    # With the plain bias some tokens land here; rows outside `valid`
    # are routed nowhere.
    out, counts = moe_held_inference(y, blk, spec)
    assert 0 < int(counts[0]) <= 24 * dm["top_k"] and int(counts[2]) >= 1
    valid = jnp.arange(24) < 6
    part, fewer = moe_held_inference(y, blk, spec, valid)
    assert int(fewer[0]) < int(counts[0])
    np.testing.assert_allclose(part[:6], out[:6], atol=1e-6)
    np.testing.assert_array_equal(part[6:], swiglu(y, blk["shared"])[6:])


def requests(dm, n=5, shared_prefix=0):
    rng = np.random.default_rng(9)
    head = rng.integers(0, dm["vocab"], shared_prefix).astype(np.int32)
    return [Request(rid=i, prompt=np.concatenate([head, rng.integers(
        0, dm["vocab"], 9 + 7 * i).astype(np.int32)]), max_new_tokens=6 + i)
            for i in range(n)]


def engine(served, **kw):
    cfg, dm, model, params = served
    return PagedEngine(model, params, slots=3, num_pages=40, page_size=8,
                       prefill_chunk=16, cache_dtype="float32", max_len=160,
                       **kw)


@pytest.mark.parametrize("feature", ["prefix", "spec"])
def test_page_features_run_on_latent_rows(served, feature):
    dm = served[1]
    plain = engine(served).run(requests(dm, shared_prefix=20))
    if feature == "prefix":     # shared pages, copy-on-write at the seam
        res = engine(served).run(requests(dm, shared_prefix=20), prefix=True)
        assert res.prefix["prefix_hit_tokens"] > 0
    else:                       # k-row verify, rejected rows rolled back
        res = engine(served, spec="lookup", spec_k=4).run(
            requests(dm, shared_prefix=20), spec=True)
        assert res.spec["spec_rounds"] > 0
    out = {r.rid: r.out for r in res.requests}
    assert out == {r.rid: r.out for r in plain.requests}
    assert all(r.status == "finished" for r in res.requests)


def spill_storm(served, host_pages, sink=None):
    """Two 16-token templates asked in alternating waves through a pool
    of 8 usable pages: wave k meets the template of wave k-2, whose
    pages wave k-1's pressure evicted (tests/test_host_tier.py's storm,
    on latent rows)."""
    from mpi_cuda_cnn_tpu.faults import FakeClock

    _, dm, model, params = served
    rng = np.random.default_rng(11)
    tmpl = [rng.integers(0, dm["vocab"], 16).astype(np.int32)
            for _ in range(2)]
    reqs = [Request(rid=2 * wave + j, arrival=wave * 2.0, max_new_tokens=13,
                    prompt=np.concatenate([tmpl[wave % 2], rng.integers(
                        0, dm["vocab"], 4).astype(np.int32)]))
            for wave in range(4) for j in range(2)]
    clk = FakeClock()
    eng = PagedEngine(model, params, slots=2, num_pages=9, page_size=8,
                      prefill_chunk=8, cache_dtype="float32", max_len=64)
    return reqs, eng.run(reqs, prefix=True, host_pages=host_pages,
                         tick_sink=sink, time_fn=clk, sleep_fn=clk.advance)


def test_spill_and_readmit_move_latent_rows(served):
    """Host tier: an evicted page's latent rows go to the host and come
    back by name (`spill_page` / `readmit_page`); the tokens are those
    of the run that prefills them again."""
    _, off = spill_storm(served, 0)
    _, on = spill_storm(served, 8)
    assert on.prefix["tier_spills"] > 0 and on.prefix["tier_readmits"] > 0
    assert on.prefix["tier_refusals"] == 0
    assert on.prefill_chunks < off.prefill_chunks
    assert ({r.rid: r.out for r in on.requests}
            == {r.rid: r.out for r in off.requests})


def test_the_replay_mirror_follows_a_latent_run(served):
    """obs/replay's mirror re-derives pages, tree and tier from the tick
    records alone: a latent run's trail (counters on its records and
    all) folds with no drift."""
    from mpi_cuda_cnn_tpu.obs.replay import RunReplay

    ticks = []
    reqs, res = spill_storm(served, 8, ticks.append)
    assert any("moe_assignments" in t for t in ticks)
    records = [{"event": "tick", **t} for t in ticks]
    records += [{"event": "request", **r} for r in res.request_records()]
    records.append({"event": "serve", "mode": res.mode, "slots": 2,
                    "pages": 9, "page_size": 8, "max_len": 64,
                    "prefix_cache": True, "host_pages": 8})
    replay = RunReplay(records).fold()
    assert replay.ticks_checked == len(ticks) > 0


def test_handoff_moves_latent_rows_between_engines(served):
    """Disaggregated prefill -> decode: `adopt_pages` copies the pool
    `c` by name from the sender's engine; the decode replica's tokens
    are the unified fleet's."""
    from mpi_cuda_cnn_tpu.serve.fleet import (
        EngineCompute,
        Fleet,
        make_fleet_workload,
    )

    _, dm, model, params = served
    geom = dict(slots=2, num_pages=17, page_size=4, max_len=48)

    def reqs():
        return make_fleet_workload(n=8, vocab=dm["vocab"], prompt_min=6,
                                   prompt_max=12, out_min=4, out_max=8,
                                   rate=300.0, seed=3)

    def factory(name):
        return EngineCompute(PagedEngine(
            model, params, prefill_chunk=8, cache_dtype="float32", **geom))

    disagg = Fleet(factory, pools={"prefill": 1, "decode": 1},
                   handoff_ticks=2, **geom).run(reqs())
    unified = Fleet(factory, replicas=2, **geom).run(reqs())
    assert disagg.handoffs > 0
    assert disagg.status_counts() == {"finished": 8}
    assert disagg.outputs() == unified.outputs()


def test_what_cannot_hold_latent_rows_refuses_at_construction(served):
    _, _, model, params = served
    with pytest.raises(ValueError, match="latent-attention model's page"):
        PagedEngine(model, params, cache_dtype="int8")


def test_the_tick_record_carries_the_counters(served):
    dm = served[1]
    ticks = []
    engine(served).run(requests(dm), tick_sink=ticks.append)
    decoded = [t for t in ticks if t["decoded"]]
    latent = set(TICK_COUNTS) - {"kv_rows_read"}
    assert decoded and all(set(TICK_COUNTS) & set(t) == latent
                           for t in decoded)
    assert not any(set(TICK_COUNTS) & set(t) for t in ticks
                   if not t["decoded"])
    # Dead slots route nothing: pairs <= live rows x top_k x layers.
    # The engine's table here (3 slots x 20 pages of 8) is read whole,
    # as the code itself chooses: every table row, every tick.
    for t in decoded:
        assert t["moe_assignments"] <= len(t["decoded"]) * dm["top_k"] * 2
        assert t["moe_experts_hit"] <= 2 * dm["held"]
        assert t["latent_rows_read"] == dm["layers"] * 3 * 160
    assert sum(t["moe_assignments"] for t in decoded) > 0


def test_latent_rows_read_is_the_hand_count_on_three_slots(served, loop):
    """paged_forward's count over a three-slot tick with the loop on:
    slot 0 at position 37 (3 blocks of 16 rows), slot 1 dead (no item:
    the latent read skips it), slot 2 at 16 (2): 5 items = 2 steps of
    3, 16 rows each, in each of the model's layers."""
    _, dm, model, params = served
    cache = init_paged_cache(model, slots=3, num_pages=3 * 12 + 1,
                             page_size=8, max_len=96)
    table = 1 + np.arange(36, dtype=np.int32).reshape(3, 12)
    table[1] = 0
    cache = dataclasses.replace(cache, block_table=jnp.asarray(table))
    _, cache = paged_forward(
        model, params, jnp.zeros((3, 1), jnp.int32),
        jnp.asarray([[37], [0], [16]], jnp.int32),
        jnp.asarray([[True], [False], [True]]), cache)
    assert loop_rows([37, 16], 8) == 2 * 3 * 16
    assert np.asarray(cache.counts).tolist()[-1] == dm["layers"] * 2 * 3 * 16


def test_latent_depths_cross_every_block_in_one_tick_and_one_prefill(
        served, loop):
    """The bound is a value inside the program: with the loop on, slots
    that grow across every page, block and step boundary compile
    nothing after the engine's first tick and first chunk, the tick
    records' `latent_rows_read` follows the depths (never more than the
    whole table plus a step's rounding), and the tokens are the
    whole-table read's."""
    dm = served[1]
    plain = engine(served)
    with pytest.MonkeyPatch.context() as mp:    # the same engine, read whole
        mp.setattr(paged_cache, "read_step",
                   lambda slots, npages, *a, **k: (npages, slots))
        want = {r.rid: r.out for r in plain.run(requests(dm)).requests}
    eng = engine(served)
    eng.run(requests(dm, n=1))                           # compiles both
    warm = eng.compiled_programs()
    assert warm == 2
    ticks = []
    res = eng.run(requests(dm), tick_sink=ticks.append)
    assert eng.compiled_programs() == warm
    assert all(t["compiled"] == 0 for t in ticks)
    assert {r.rid: r.out for r in res.requests} == want
    rows = [t["latent_rows_read"] for t in ticks if t["decoded"]]
    whole = dm["layers"] * 3 * 160
    width = LOOP[0] * 8
    assert max(rows) <= whole + dm["layers"] * LOOP[1] * width
    assert min(rows) < whole / 2 and len(set(rows)) >= 3    # it follows depth


def test_a_kv_model_counts_its_rows_and_nothing_else():
    from mpi_cuda_cnn_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab=64, dim=32, heads=4, depth=2, max_seq=64)
    eng = PagedEngine(model, model.init(jax.random.key(0)), slots=2,
                      num_pages=9, page_size=8, max_len=64)
    ticks = []
    eng.run([Request(rid=0, prompt=np.arange(9, dtype=np.int32),
                     max_new_tokens=4)], tick_sink=ticks.append)
    decoded = [t for t in ticks if t["decoded"]]
    # A table this small is read whole: 2 layers x 2 slots x 64 rows.
    assert decoded and all(
        set(TICK_COUNTS) & set(t) == {"kv_rows_read"}
        and t["kv_rows_read"] == 2 * 2 * 64 for t in decoded)
    assert not any(set(TICK_COUNTS) & set(t) for t in ticks
                   if not t["decoded"])


def test_a_latent_model_is_not_for_the_trainers_or_the_contiguous_cache(
        served):
    from mpi_cuda_cnn_tpu.models.generate import init_cache

    _, _, model, params = served
    with pytest.raises(ValueError, match="latent"):
        model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="latent"):
        model.apply(params, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(ValueError, match="paged cache"):
        init_cache(model, 1)


# -- the GPT-2 family's golden numbers (recorded before PR 27's move) --------

from benchmarks.tests import test_families  # noqa: E402


@pytest.mark.parametrize("name", sorted(test_families.GOLDEN["tiny"]))
def test_gpt2_golden_numbers_unchanged(name):
    test_families.test_gpt2_weights_and_reference_as_before_the_move(name)


@pytest.mark.parametrize("name", sorted(test_families.GOLDEN["work"]))
def test_gpt2_golden_work_counts_unchanged(name):
    test_families.test_gpt2_work_counts_as_before_the_move(name)


# -- the three per-layer readers ---------------------------------------------

class FakeTrace:
    def __init__(self, tick_runs):
        self.runs = tick_runs

    def module_durations(self, name):
        return self.runs if name == "jit_tick" else []


def reader(name):
    return run.load_named(BENCH, "layer_metrics", name).read


def test_readers_read_the_counters_and_nothing_where_there_are_none(served):
    cfg, dm, _, _ = served
    tick = {"prefill": [], "finished": [], "preempted": [], "aborted": []}
    ticks = [
        {**tick, "prefill": [0, 7, 5], "decoded": []},
        {**tick, "decoded": [[0, 7]], "moe_assignments": 3,
         "moe_experts_hit": 2, "moe_load_max": 2, "latent_rows_read": 960},
        # Request 8 prefills whole and decodes in one iteration.
        {**tick, "prefill": [1, 8, 3, "emit"], "decoded": [[0, 7], [1, 8]],
         "moe_assignments": 1, "moe_experts_hit": 1, "moe_load_max": 1,
         "latent_rows_read": 960},
    ]
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    ctx = {"ticks": ticks, "dims": dm, "family": FAM, "config": cfg,
           "peaks": peaks, "first_traced": 2, "trace": FakeTrace([1e-3, 2e-4])}
    slots = dm["held"] * (dm["layers"] - dm["dense_layers"])      # 4 x 2
    assert reader("expert_load_mean")(ctx) == pytest.approx(2 / slots)
    assert reader("experts_hit_share")(ctx) == pytest.approx(
        100 * 1.5 / slots)
    # Only the last record is in the traced slice: one row at depth 6
    # (5 prefilled + 1 decoded) reading 7 rows and one at depth 3
    # reading 4, against the LAST run.
    least = FAM.work.tick_least_seconds(
        dm, peaks, rows=2, contexts=11, assignments=1, experts_hit=1,
        weight_bytes=4, cache_bytes=4)
    assert reader("tick_roofline")(ctx) == pytest.approx(100 * least / 2e-4)
    assert 0 < reader("tick_roofline")(ctx) < 100
    # A program (or a family) without the counters: nothing, no raise.
    bare = [{k: v for k, v in t.items() if not k.startswith(("moe", "latent"))}
            for t in ticks]
    for name in ("expert_load_mean", "experts_hit_share", "tick_roofline"):
        assert reader(name)({**ctx, "ticks": bare}) is None
    gpt2 = run.load_family(BENCH / "families" / "gpt2")
    assert reader("tick_roofline")({**ctx, "family": gpt2}) is None


# -- the family's tiny benchmark --------------------------------------------

def run_tiny(**kw):
    return run.run_cell("tiny-mla.mix", seed=SEED, seconds=3, trace=False,
                        bench_file=TINY / "bench.json", require_chip=False,
                        **kw)


def test_tiny_benchmark_program_correct_control_not():
    line = run_tiny(lower="fp8")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    control = line["control"]
    assert control["correct"] is False, control
    # ... by the mean gap: at this size the largest gap cannot part the
    # two (the cell file's `limits_from`).
    gap_mean = control["compared"]["gap_mean"]
    assert gap_mean["value"] > 2 * gap_mean["limit"], control
    assert set(line["metrics"]) == {"tokens_per_s", "tpot_p95_ms", "setup_s"}
