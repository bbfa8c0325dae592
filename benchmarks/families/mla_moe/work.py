"""Operations and bytes this family's block needs, from shapes alone. A
multiply-add is 2 operations; padding, dead slots and empty cache rows
count for nothing.

Attention is counted in the ABSORBED form, for decode ticks and prefill
chunks alike (the one read the program has, models/generate.attend_latent;
the cheaper form while a slot's queries are under ~150): the query's
nope part is taken through W_uk once a token (heads x nope x kv_rank),
every key costs heads x (kv_rank + rope) for the score and heads x
kv_rank for the value, and the output goes through W_uv once a token
(heads x kv_rank x v). That is the least the mathematics needs once
keys are not up-projected again for every query; the materialized form
of the same read costs kv_rank x heads x (nope + v) more for EVERY key.

The expert layer is counted at what this chip's share computes: the
router over all `routed` experts, the shared expert, and the EXPECTED
top_k x held / routed held experts a token (0.5 at 8 x 16 / 256; the
routing is seeded, near enough uniform); `tick_work` counts the pairs a
tick really computed, from the program's counters.
"""

from __future__ import annotations


def _attn_params(dm: dict) -> int:
    """Multiply-adds a token in one layer's attention, keys apart."""
    w, h = dm["width"], dm["heads"]
    return (w * dm["q_rank"] + dm["q_rank"] * h * (dm["nope"] + dm["rope"])
            + w * (dm["kv_rank"] + dm["rope"])
            + h * dm["nope"] * dm["kv_rank"] + h * dm["kv_rank"] * dm["v"]
            + h * dm["v"] * w)


def _per_key(dm: dict) -> int:
    """Multiply-adds a key a layer: score over the row, value over c."""
    return dm["heads"] * (2 * dm["kv_rank"] + dm["rope"])


def _expert(dm: dict) -> int:
    return 3 * dm["width"] * dm["expert_mlp"]


def _layer_params(dm: dict, held_per_token: float) -> float:
    """Multiply-adds a token over all layers and the head, keys apart,
    with `held_per_token` held experts computed a token a layer."""
    w = dm["width"]
    dense, routed = dm["dense_layers"], dm["layers"] - dm["dense_layers"]
    return (dm["layers"] * _attn_params(dm)
            + dense * 3 * w * dm["dense_mlp"]
            + routed * (w * dm["routed"] + 3 * w * dm["shared_mlp"]
                        + held_per_token * _expert(dm))
            + w * dm["vocab"])


def held_slots(dm: dict) -> int:
    """Held experts x expert layers: what the tick's `moe_assignments`
    and `moe_experts_hit` are spread over."""
    return dm["held"] * (dm["layers"] - dm["dense_layers"])


def matmul_shapes(dm: dict) -> list[tuple[int, int, int]]:
    """(din, dout, calls per forward) of every weight matmul that
    `qmatmul` dispatches (the per-head absorbed products, the router
    and the grouped expert products are not among them)."""
    w, h, n = dm["width"], dm["heads"], dm["layers"]
    dense, routed = dm["dense_layers"], n - dm["dense_layers"]
    return [(w, dm["q_rank"], n),
            (dm["q_rank"], h * (dm["nope"] + dm["rope"]), n),
            (w, dm["kv_rank"] + dm["rope"], n), (h * dm["v"], w, n),
            (w, dm["dense_mlp"], 2 * dense), (dm["dense_mlp"], w, dense),
            (w, dm["shared_mlp"], 2 * routed), (dm["shared_mlp"], w, routed),
            (w, dm["vocab"], 1)]


def span_flops(dm: dict, start: int, n: int) -> int:
    """Model FLOPs of n consecutive tokens at positions start..start+n-1
    (contexts start+1 .. start+n), at the expected share of experts."""
    contexts = n * start + n * (n + 1) // 2
    expected = dm["top_k"] * dm["held"] / dm["routed"]
    return int(2 * _layer_params(dm, expected) * n
               + 2 * dm["layers"] * _per_key(dm) * contexts)


def token_flops(dm: dict, context: int) -> int:
    return span_flops(dm, context - 1, 1)


def tick_work(dm: dict, *, rows: int, contexts: int, assignments: int,
              experts_hit: int, weight_bytes: int = 2,
              cache_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one decode tick: `rows` live slots, one
    token each, reading `contexts` cache rows in all (each slot's own
    depth, summed; every layer reads them), with `assignments`
    token-expert pairs computed and `experts_hit` held experts touched,
    both summed over the expert layers (the tick record's counters).
    Bytes: every weight outside the expert banks once (the router's in
    f32), the touched experts' matrices once, the live latent rows once
    a layer; activations count for nothing beside them."""
    routers = (dm["layers"] - dm["dense_layers"]) * dm["width"] * dm["routed"]
    outside = int(_layer_params(dm, 0.0))
    ops = (2 * outside * rows + 2 * _expert(dm) * assignments
           + 2 * dm["layers"] * _per_key(dm) * contexts)
    moved = (weight_bytes * (outside - routers) + 4 * routers
             + weight_bytes * _expert(dm) * experts_hit
             + cache_bytes * dm["layers"] * contexts
             * (dm["kv_rank"] + dm["rope"]))
    return ops, moved


def tick_least_seconds(dm: dict, peaks: dict, **tick) -> float:
    """The least time the chip could take for that tick: the larger of
    operations over the bf16 peak and bytes over the HBM peak."""
    ops, moved = tick_work(dm, **tick)
    return max(ops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])


def check() -> None:
    """Against counts written out by hand at one small shape."""
    dm = {"width": 8, "heads": 2, "q_rank": 4, "kv_rank": 6, "nope": 3,
          "rope": 2, "v": 5, "layers": 3, "dense_layers": 1, "dense_mlp": 16,
          "expert_mlp": 4, "shared_mlp": 4, "routed": 8, "held": 2,
          "top_k": 2, "vocab": 10}
    # A layer's attention: 8x4 + 4x(2x5) + 8x(6+2) + absorb 2x3x6 +
    # values 2x6x5 + out (2x5)x8 = 32 + 40 + 64 + 36 + 60 + 80 = 312.
    assert _attn_params(dm) == 312
    # A key: 2 heads x (6 + 2 score + 6 value) = 28.
    assert _per_key(dm) == 28
    # Dense layer MLP 3x8x16 = 384; an expert layer outside its bank:
    # router 8x8 = 64, shared 3x8x4 = 96; an expert 96; head 80.
    outside = 3 * 312 + 384 + 2 * (64 + 96) + 80
    assert _layer_params(dm, 0.0) == outside == 1720
    # Expected held experts a token: 2 x 2 / 8 = 0.5, in 2 layers: 96.
    # 2 tokens at positions 5, 6: contexts 6 + 7.
    want = 2 * 2 * (outside + 96) + 2 * 3 * 28 * 13
    assert span_flops(dm, 5, 2) == want
    assert token_flops(dm, 6) + token_flops(dm, 7) == want
    assert sum(din * dout * n for din, dout, n in matmul_shapes(dm)) == (
        outside - 2 * 64 - 3 * (36 + 60))
    # A tick of 4 rows over 20 cache rows, 3 pairs on 2 experts, bf16:
    ops, moved = tick_work(dm, rows=4, contexts=20, assignments=3,
                           experts_hit=2)
    assert ops == 2 * outside * 4 + 2 * 96 * 3 + 2 * 3 * 28 * 20
    assert moved == (2 * (outside - 128) + 4 * 128 + 2 * 96 * 2
                     + 2 * 3 * 20 * 8)
    assert tick_least_seconds(
        dm, {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e2}, rows=4,
        contexts=20, assignments=3, experts_hit=2) == max(ops / 1e3,
                                                          moved / 1e2)
