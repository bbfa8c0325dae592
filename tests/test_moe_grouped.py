"""The three forms of moe_held_inference's grouped products.

One Pallas kernel visits every (row tile, expert) that meets and
streams each touched expert once a visit (ops/pallas_expert_mlp,
interpreted on the CPU), its matrices whole where they fit the kernel's
VMEM and in width slices where they do not; where no slice fits, the
sorted pairs are walked in 128-row steps of lax.ragged_dot.
Which form a program holds is read off shapes alone. Each is held
here to the walk and to the plain product that puts every token
through every expert.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_cnn_tpu.models.transformer import RoutedExperts
from mpi_cuda_cnn_tpu.ops.pallas_expert_mlp import (
    ALIGN,
    ROW_TILE,
    fits,
    visits,
    width_slice,
    windows,
)
from mpi_cuda_cnn_tpu.parallel import ep

DIM, WIDTH = 32, 16
ALL_HELD = RoutedExperts(experts=8, held=tuple(range(8)), top_k=2,
                         router="softmax", act="relu", reads="layer_input")
# A share of a grouped sigmoid router's layer: ids 32..47 of 256.
SHARE = RoutedExperts(experts=256, held=tuple(range(32, 48)), top_k=8,
                      groups=8, top_groups=4, scale=2.5)


def block(spec, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    n = len(spec.held)
    router = {"gate": jax.random.normal(ks[0], (DIM, spec.experts))}
    if spec.router == "sigmoid":
        router["bias"] = 0.1 * jax.random.normal(ks[1], (spec.experts,))
    return {"router": router, "experts": {
        "wg": jax.random.normal(ks[2], (n, DIM, WIDTH)) / 6,
        "wu": jax.random.normal(ks[3], (n, DIM, WIDTH)) / 6,
        "wd": jax.random.normal(ks[4], (n, WIDTH, DIM)) / 4}}


def every_token_through_every_expert(x, blk, spec, ids, w, valid):
    """The plain product: each held expert's MLP on all rows, weighted
    by what the routing gave that expert (0 for most rows)."""
    bank, act = blk["experts"], ep._GATE_ACT[spec.act]
    if valid is not None:
        w = jnp.where(valid[:, None], w, 0.0)
    with jax.default_matmul_precision("highest"):
        return sum(
            jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1, keepdims=True)
            * ((act(x @ bank["wg"][i]) * (x @ bank["wu"][i])) @ bank["wd"][i])
            for i, e in enumerate(spec.held))


def _uniform(x, blk, spec):
    return None


def _one_expert(x, blk, spec):
    t = x.shape[0]
    w = jax.random.uniform(jax.random.key(3), (t, spec.top_k)) + 0.1
    return jnp.full((t, spec.top_k), 5, jnp.int32), w


def _experts_with_no_pair(x, blk, spec):
    ids, w = ep.route(x, blk["router"], spec)
    return jnp.where(ids % 2 == 0, 0, 5).astype(jnp.int32), w


def _from_outside(x, blk, spec):
    t = x.shape[0]
    ids = jax.random.randint(jax.random.key(4), (t, spec.top_k), 0,
                             spec.experts, jnp.int32)
    return ids, jax.random.uniform(jax.random.key(5), (t, spec.top_k))


CASES = {
    # (spec, rows, routing given from outside or None, valid rows or None)
    "uniform": (ALL_HELD, 512, _uniform, None),
    "one_expert": (ALL_HELD, 512, _one_expert, None),
    "experts_with_no_pair": (ALL_HELD, 512, _experts_with_no_pair, None),
    "valid_1_of_512": (ALL_HELD, 512, _uniform, 1),
    "valid_511_of_512": (ALL_HELD, 512, _uniform, 511),
    "valid_512_of_512": (ALL_HELD, 512, _uniform, 512),
    "held_share_by_row_count": (SHARE, 2560, _uniform, None),
    "routing_from_outside": (ALL_HELD, 512, _from_outside, None),
    # A decode tick's one step: 128 pair rows in one tile, every expert
    # held; and 256 pair rows of which ~16 land on a held share.
    "one_step_all_held": (ALL_HELD, 64, _uniform, None),
    "one_step_held_share": (SHARE, 32, _uniform, None),
}


def held_to_the_walk_and_the_plain_product(case, scope, monkeypatch):
    """The kernel's form (`scope`) against the walk and the plain
    product in one of CASES."""
    spec, rows, routed, n_valid = CASES[case]
    blk = block(spec)
    x = jax.random.normal(jax.random.key(7), (rows, DIM))
    routing = routed(x, blk, spec)
    valid = None if n_valid is None else jnp.arange(rows) < n_valid
    text = jax.jit(lambda x: ep.moe_held_inference(
        x, blk, spec, valid, routing)).lower(x).as_text(debug_info=True)
    assert scope in text and "ragged_dot" not in text
    got, counts = ep.moe_held_inference(x, blk, spec, valid, routing)
    # ... and the walk is what it was without it.
    monkeypatch.setattr(ep, "tiled_products", lambda *a: False)
    text = jax.jit(lambda x: ep.moe_held_inference(
        x, blk, spec, valid, routing)).lower(x).as_text(debug_info=True)
    assert "ep.held_experts.grouped" not in text
    assert "ep.held_experts.sliced" not in text and "ragged_dot" in text
    walked, walk_counts = ep.moe_held_inference(x, blk, spec, valid, routing)
    np.testing.assert_array_equal(counts, walk_counts)
    np.testing.assert_allclose(got, walked, atol=2e-5, rtol=1e-5)
    ids, w = routing if routing is not None else ep.route(
        x, blk["router"], spec)
    want = every_token_through_every_expert(x, blk, spec, ids, w, valid)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=1e-5)
    landed = np.isin(np.asarray(ids), spec.held)
    if valid is not None:
        landed &= np.asarray(valid)[:, None]
    hit = np.unique(np.asarray(ids)[landed])
    assert counts.tolist() == [
        landed.sum(), hit.size,
        max((np.asarray(ids)[landed] == e).sum() for e in hit)]
    if valid is not None:
        np.testing.assert_array_equal(got[n_valid:], 0.0)


@pytest.mark.parametrize("case", CASES)
def test_the_tiled_form_is_the_walk_and_the_plain_product(case, monkeypatch):
    spec = CASES[case][0]
    # The rule, not a flag, picks the kernel's whole matrices here ...
    assert ep.tiled_products(block(spec)["experts"]) == WIDTH
    held_to_the_walk_and_the_plain_product(
        case, "ep.held_experts.grouped", monkeypatch)


@pytest.mark.parametrize("case", CASES)
def test_the_sliced_form_is_the_walk_and_the_plain_product(case,
                                                           monkeypatch):
    """Matrices too wide for the kernel's VMEM, as if the room held
    half an expert's width: two slices a visit, summed in f32 before
    the expert's rows are written; every case's experts straddle tiles
    (uniform: ~128 rows an expert over 128-row tiles)."""
    spec = CASES[case][0]
    monkeypatch.setattr(ep, "width_slice", lambda dim, width, b: width // 2)
    assert ep.tiled_products(block(spec)["experts"]) == WIDTH // 2
    held_to_the_walk_and_the_plain_product(
        case, "ep.held_experts.sliced", monkeypatch)


def test_the_form_is_read_off_shapes_alone():
    """The kernel wherever an expert's matrices fit it, whatever the
    pairs that can land (rows x top_k x held / experts) — the rule reads
    the bank's shapes and nothing else: width slices of 512 for
    dots.vlm1's 16 of 256 experts of (7168, 2048) at its 32-row chunk
    (16 pairs) and 64-row tick (32); slices of 1,024 for Command A+'s 16
    of 128 of (4096, 4096) at its 48-row tick (48) and 512-row chunk
    (512); whole matrices for smallthinker's 64 of (2560, 768) at a
    16-row tick (96) and a 512-row chunk (3,072), and for xing4.0's 64
    of (3584, 1024) at its 32-row tick (128). The walk where not even
    128 columns fit twice."""
    bank = lambda n, d, w: {"wg": jax.ShapeDtypeStruct(
        (n, d, w), jnp.bfloat16)}
    assert ep.tiled_products(bank(16, 7168, 2048)) == 512
    assert ep.tiled_products(bank(16, 4096, 4096)) == 1024
    assert ep.tiled_products(bank(64, 2560, 768)) == 768
    assert ep.tiled_products(bank(64, 3584, 1024)) == 1024
    assert not ep.tiled_products(bank(16, 65536, 4096))
    # One layer's program at each of those ticks holds the kernel's
    # form and no step of the walk.
    for spec, rows, (d, w), form in (
            (RoutedExperts(experts=256, held=tuple(range(16)), top_k=8,
                           groups=8, top_groups=4, scale=2.5), 64,
             (7168, 2048), "sliced"),
            (RoutedExperts(experts=128, held=tuple(range(16)), top_k=8),
             48, (4096, 4096), "sliced"),
            (RoutedExperts(experts=64, held=tuple(range(64)), top_k=6,
                           router="softmax", act="relu",
                           reads="layer_input"), 16, (2560, 768), "grouped"),
            (RoutedExperts(experts=64, held=tuple(range(64)), top_k=4), 32,
             (3584, 1024), "grouped")):
        n = len(spec.held)
        shapes = {"wg": (n, d, w), "wu": (n, d, w), "wd": (n, w, d)}
        blk = {"experts": {m: jax.ShapeDtypeStruct(s, jnp.bfloat16)
                           for m, s in shapes.items()},
               "router": {"gate": jax.ShapeDtypeStruct(
                              (d, spec.experts), jnp.float32),
                          "bias": jax.ShapeDtypeStruct(
                              (spec.experts,), jnp.float32)}}
        text = jax.jit(lambda x, blk: ep.moe_held_inference(
            x, blk, spec)).lower(jax.ShapeDtypeStruct(
                (rows, d), jnp.bfloat16), blk).as_text(debug_info=True)
        assert f"ep.held_experts.{form}" in text
        assert "ragged_dot" not in text


@pytest.mark.parametrize("dim, width, itemsize, want", [
    (2560, 768, 2, 768),      # smallthinker: whole, 2 x 11.8 MB
    (4096, 4096, 2, 1024),    # Command A+: 2 x 3 x 8 MiB of slices
    (7168, 2048, 2, 512),     # dots.vlm1
    (4096, 4096, 4, 512),     # the same in f32: half the columns
    (65536, 4096, 2, 0),      # not even 128 columns fit twice
    (4096, 4000, 2, 0),       # no multiple of 128 lanes divides 4,000
])
def test_a_slice_is_the_widest_that_fits_and_tiles_the_width(
        dim, width, itemsize, want):
    assert width_slice(dim, width, itemsize) == want
    if want:
        assert width % want == 0
        assert fits(dim, want, itemsize)
        assert want == width or not fits(dim, 2 * want, itemsize)


@pytest.mark.parametrize("sizes", [
    [48] * 8, [0, 0, 300, 0, 1, 0, 83, 0], [0] * 8, [384] + [0] * 7,
    [127, 1, 128, 0, 0, 127, 0, 1], [1] + [0] * 7, [0] * 7 + [5]])
def test_the_kernel_visits_every_tile_and_expert_that_meet_once(sizes):
    sizes = np.asarray(sizes, np.int32)
    rows = 384
    starts, expert, row_tile, count = jax.jit(
        lambda s: visits(s, rows, ROW_TILE))(jnp.asarray(sizes))
    ends = np.cumsum(sizes)
    np.testing.assert_array_equal(starts, [0, *ends])
    want = [(r // ROW_TILE, e) for e in range(8)
            for r in range(ends[e] - sizes[e], ends[e])]
    want = sorted(set(want), key=lambda te: (te[1], te[0]))
    assert expert.shape == row_tile.shape == (rows // ROW_TILE + 8 - 1,)
    assert int(count) == len(want) <= expert.shape[0]
    got = list(zip(np.asarray(row_tile)[:len(want)].tolist(),
                   np.asarray(expert)[:len(want)].tolist()))
    assert got == want
    # Past the last visit nothing new is asked for: no fetch.
    if want:
        assert set(zip(np.asarray(row_tile)[len(want):].tolist(),
                       np.asarray(expert)[len(want):].tolist())) <= {want[-1]}


@pytest.mark.parametrize("sizes", [
    [48] * 8, [0, 0, 300, 0, 1, 0, 83, 0], [0] * 8, [384] + [0] * 7,
    [127, 1, 128, 0, 0, 127, 0, 1], [1] + [0] * 7, [0] * 7 + [5],
    [0] * 6 + [100, 284]])
def test_the_sliced_kernel_takes_each_expert_in_windows_of_its_own(sizes):
    """A window is `tile` rows from an expert's first row rounded down
    to ALIGN, the next `tile` on; one that would pass the rows' end is
    moved back to end there. Every expert with rows gets the fewest
    windows that cover them, and no expert shares a window's weights
    with another."""
    sizes = np.asarray(sizes, np.int32)
    rows = 384
    starts, expert, first, count = jax.jit(
        lambda s: windows(s, rows, ROW_TILE))(jnp.asarray(sizes))
    ends = np.cumsum(sizes)
    np.testing.assert_array_equal(starts, [0, *ends])
    want = []
    for e in range(8):
        if sizes[e]:
            lo = (ends[e] - sizes[e]) // ALIGN * ALIGN
            want += [(e, min(at, rows - ROW_TILE))
                     for at in range(lo, ends[e], ROW_TILE)]
    most = rows // ROW_TILE + 8 + -(-8 * (ALIGN - 1) // ROW_TILE)
    assert expert.shape == first.shape == (most,)
    assert int(count) == len(want) <= most
    got = list(zip(np.asarray(expert)[:len(want)].tolist(),
                   np.asarray(first)[:len(want)].tolist()))
    assert got == want
    assert all(f % ALIGN == 0 for _, f in got)
    for e in range(8):       # the windows cover every row of the expert
        mine = {r for x, f in got if x == e for r in range(f, f + ROW_TILE)}
        assert set(range(ends[e] - sizes[e], ends[e])) <= mine
    if want:
        assert set(zip(np.asarray(expert)[len(want):].tolist(),
                       np.asarray(first)[len(want):].tolist())) <= {want[-1]}
