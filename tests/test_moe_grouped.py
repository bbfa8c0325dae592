"""The two forms of moe_held_inference's grouped products (ISSUE 33).

Where few (token, expert) pairs can land on this chip the sorted pairs
are walked in 128-row steps of lax.ragged_dot; where many can, one
Pallas kernel visits every (row tile, expert) that meets and streams
each touched expert once (ops/pallas_expert_mlp, interpreted on the
CPU).
Which form a program holds is read off shapes alone. Both are held
here to each other and to the plain product that puts every token
through every expert.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_cnn_tpu.models.transformer import RoutedExperts
from mpi_cuda_cnn_tpu.ops.pallas_expert_mlp import ROW_TILE, visits
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
}


@pytest.mark.parametrize("case", CASES)
def test_the_tiled_form_is_the_walk_and_the_plain_product(case, monkeypatch):
    spec, rows, routed, n_valid = CASES[case]
    blk = block(spec)
    x = jax.random.normal(jax.random.key(7), (rows, DIM))
    routing = routed(x, blk, spec)
    valid = None if n_valid is None else jnp.arange(rows) < n_valid
    # The rule, not a flag, picks the kernel at these shapes ...
    assert ep.tiled_products(rows, spec, blk["experts"])
    text = jax.jit(lambda x: ep.moe_held_inference(
        x, blk, spec, valid, routing)).lower(x).as_text(debug_info=True)
    assert "ep.held_experts.grouped" in text and "ragged_dot" not in text
    got, counts = ep.moe_held_inference(x, blk, spec, valid, routing)
    # ... and the walk is what it was without it.
    monkeypatch.setattr(ep, "tiled_products", lambda *a: False)
    text = jax.jit(lambda x: ep.moe_held_inference(
        x, blk, spec, valid, routing)).lower(x).as_text(debug_info=True)
    assert "ep.held_experts.grouped" not in text and "ragged_dot" in text
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


def test_the_form_is_read_off_shapes_alone():
    """Pairs that can land: rows x top_k x held / experts. The walk at
    dots.vlm1's 16 and 32 (its 32-row chunk and 64-row tick over 16 of
    256 experts, top-8), the tiles at smallthinker's 3,072 (a 512-row
    chunk, all 64 held, top-6); an expert too large for the kernel's
    VMEM keeps the walk whatever lands."""
    dots = RoutedExperts(experts=256, held=tuple(range(16)), top_k=8,
                         groups=8, top_groups=4, scale=2.5)
    small = RoutedExperts(experts=64, held=tuple(range(64)), top_k=6,
                          router="softmax", act="relu", reads="layer_input")
    bank = lambda n, d, w: {"wg": jax.ShapeDtypeStruct(
        (n, d, w), jnp.bfloat16)}
    assert ep.pairs_landing(32, dots) == 16
    assert ep.pairs_landing(64, dots) == 32
    assert ep.pairs_landing(512, small) == 3072
    assert ep.pairs_landing(32, small) == 192
    assert not ep.tiled_products(32, dots, bank(16, 7168, 2048))
    assert not ep.tiled_products(64, dots, bank(16, 7168, 2048))
    assert ep.tiled_products(512, small, bank(64, 2560, 768))
    assert not ep.tiled_products(16, small, bank(64, 2560, 768))
    assert not ep.tiled_products(1 << 16, dots, bank(16, 7168, 2048))
    # Nothing but the shapes is asked: a spec with other names for the
    # same sizes answers the same.
    other = dataclasses.replace(small, router="sigmoid", act="silu",
                                reads="block")
    assert ep.tiled_products(512, other, bank(64, 2560, 768))


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
