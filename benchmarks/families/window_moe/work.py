"""Operations and bytes this family's block needs, from shapes alone. A
multiply-add is 2 operations; padding, dead slots and empty cache rows
count for nothing.

A token's attention reaches back over ITS OWN context: every key up to
itself in a global layer, at most `window` of them in a windowed one.
That is why nothing here takes a summed context: 10 requests at depth
1,000 and one at 10,000 read the same rows in a global layer and very
different ones in a windowed layer, so the tick's least time
(`window_tick_least_seconds`) takes each decoding request's own depth.
This family defines no `tick_least_seconds`: the accepted
`tick_roofline` reader hands that function ONE summed context, which
would count a windowed layer's bytes as a global layer's, and by its
own rule finds nothing to read where the function is not there.

The expert layer is counted at what it computes: the router over all
experts and `top_k` experts a token, all of them held here.
"""

from __future__ import annotations


def _attn_params(dm: dict) -> int:
    """Multiply-adds a token in one layer's attention, keys apart."""
    w, hd = dm["width"], dm["head_dim"]
    return w * hd * (2 * dm["heads"] + 2 * dm["kv_heads"])


def _per_key(dm: dict) -> int:
    """Multiply-adds a key a layer: score and value, every query head."""
    return 2 * dm["heads"] * dm["head_dim"]


def _expert(dm: dict) -> int:
    return 3 * dm["width"] * dm["expert_mlp"]


def _outside(dm: dict) -> int:
    """Multiply-adds a token over all layers and the head, keys and
    expert banks apart."""
    return (dm["layers"] * (_attn_params(dm) + dm["width"] * dm["experts"])
            + dm["width"] * dm["vocab"])


def _keys(dm: dict, context: int) -> int:
    """Keys a token with `context` positions up to itself reads, all
    layers together."""
    windowed = sum(dm["window_layout"])
    return ((dm["layers"] - windowed) * context
            + windowed * min(context, dm["window"]))


def held_slots(dm: dict) -> int:
    """Held experts x expert layers: what the tick's `moe_assignments`
    and `moe_experts_hit` are spread over. Every expert of every layer
    is here."""
    return dm["experts"] * dm["layers"]


def matmul_shapes(dm: dict) -> list[tuple[int, int, int]]:
    """(din, dout, calls per forward) of every weight matmul that
    `qmatmul` dispatches (the f32 router and the grouped expert
    products are not among them)."""
    w, hd, n = dm["width"], dm["head_dim"], dm["layers"]
    return [(w, dm["heads"] * hd, n), (w, 2 * dm["kv_heads"] * hd, n),
            (dm["heads"] * hd, w, n), (w, dm["vocab"], 1)]


def span_flops(dm: dict, start: int, n: int) -> int:
    """Model FLOPs of n consecutive tokens at positions start..start+n-1
    (contexts start+1 .. start+n)."""
    keys = sum(_keys(dm, c) for c in range(start + 1, start + n + 1))
    return int(2 * (_outside(dm) + dm["top_k"] * _expert(dm)) * n
               + 2 * _per_key(dm) * keys)


def token_flops(dm: dict, context: int) -> int:
    return span_flops(dm, context - 1, 1)


def window_tick_work(dm: dict, *, contexts, assignments: int,
                     experts_hit: int, weight_bytes: int = 2,
                     cache_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one decode tick: one token a decoding
    request, `contexts` each request's own context (its depth + 1),
    with `assignments` token-expert pairs computed and `experts_hit`
    experts touched, both summed over the layers (the tick record's
    counters). Bytes: every weight outside the expert banks once (the
    routers' in f32), the touched experts' matrices once, each
    request's live K and V rows once a layer -- all of them in a
    global layer, at most a window's in a windowed one; activations
    count for nothing beside them."""
    routers = dm["layers"] * dm["width"] * dm["experts"]
    keys = sum(_keys(dm, c) for c in contexts)
    ops = (2 * _outside(dm) * len(contexts) + 2 * _expert(dm) * assignments
           + 2 * _per_key(dm) * keys)
    moved = (weight_bytes * (_outside(dm) - routers) + 4 * routers
             + weight_bytes * _expert(dm) * experts_hit
             + cache_bytes * 2 * dm["kv_heads"] * dm["head_dim"] * keys)
    return ops, moved


def window_tick_least_seconds(dm: dict, peaks: dict, **tick) -> float:
    """The least time the chip could take for that tick: the larger of
    operations over the bf16 peak and bytes over the HBM peak."""
    ops, moved = window_tick_work(dm, **tick)
    return max(ops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])


def check() -> None:
    """Against counts written out by hand at one small shape."""
    dm = {"width": 8, "heads": 4, "kv_heads": 2, "head_dim": 3, "layers": 4,
          "window_layout": (0, 1, 1, 1), "window": 5, "expert_mlp": 6,
          "experts": 8, "top_k": 2, "vocab": 10}
    # A layer's attention: q 8x12, k and v 8x6 each, out 12x8 = 288.
    assert _attn_params(dm) == 96 + 48 + 48 + 96 == 288
    # A key: 4 heads x 3 for the score, the same for the value.
    assert _per_key(dm) == 24
    # Outside the banks: 4 x (288 + router 8x8) + head 80; an expert
    # 3 x 8 x 6 = 144.
    assert _outside(dm) == 4 * (288 + 64) + 80 == 1488
    assert _expert(dm) == 144
    # One global layer and three windowed: context 3 reads 4 x 3 keys,
    # context 9 reads 9 + 3 x 5.
    assert _keys(dm, 3) == 12 and _keys(dm, 9) == 24
    # 2 tokens at positions 7, 8: contexts 8, 9: (8 + 15) + (9 + 15) keys.
    want = 2 * 2 * (1488 + 2 * 144) + 2 * 24 * 47
    assert span_flops(dm, 7, 2) == want
    assert token_flops(dm, 8) + token_flops(dm, 9) == want
    assert sum(din * dout * n for din, dout, n in matmul_shapes(dm)) == (
        1488 - 4 * 64)
    assert held_slots(dm) == 32
    # A tick of requests at contexts 3 and 9, 4 pairs on 3 experts, bf16:
    ops, moved = window_tick_work(dm, contexts=[3, 9], assignments=4,
                                  experts_hit=3)
    assert ops == 2 * 1488 * 2 + 2 * 144 * 4 + 2 * 24 * 36
    assert moved == (2 * (1488 - 256) + 4 * 256 + 2 * 144 * 3
                     + 2 * 2 * 2 * 3 * 36)
    assert window_tick_least_seconds(
        dm, {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e2}, contexts=[3, 9],
        assignments=4, experts_hit=3) == max(ops / 1e3, moved / 1e2)
