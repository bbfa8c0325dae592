"""Operations and bytes this family's block needs, from shapes alone. A
multiply-add is 2 operations; padding, dead slots and empty cache rows
count for nothing.

A SPARSE layer's token at context c (positions up to itself) scores the
compressed keys complete there (one product a query head) and attends
over the keys of the blocks it chooses: all c under `dense_len`, else at
most `block x (init_blocks + topk) + window` of them. A LINEAR layer's
token reads and updates one (head_dim, head_dim) state a head, whatever
c is: 2 products of head_dim x head_dim a head. That is the model's
work; a chunk's intra-chunk products, which the program spends to run
the recurrence many rows at a time, are the program's way and count
for nothing here.

This family defines no `tick_least_seconds`: the accepted
`tick_roofline` reader hands that function ONE summed context, which
would count a linear layer's state as keys; `state_tick_least_seconds`
takes the decoding requests and the tick's counters.
"""

from __future__ import annotations

STATE_BYTES = 4     # a linear layer's state is float32 whatever the cache


def _layers(dm: dict) -> tuple[int, int]:
    """(sparse layers, linear layers)."""
    linear = sum(m == "linear" for m in dm["mixers"])
    return dm["layers"] - linear, linear


def _mixer_params(dm: dict, kind: str) -> int:
    """Multiply-adds a token in one layer's projections: q, k, v, the
    output gate and the output matrix."""
    w, hd, h = dm["width"], dm["head_dim"], dm["heads"]
    kv = h if kind == "linear" else dm["kv_heads"]
    return w * hd * (h + 2 * kv) + 2 * w * h * hd


def _mlp(dm: dict) -> int:
    return 3 * dm["width"] * dm["mlp"]


def _outside(dm: dict) -> int:
    """Multiply-adds a token over all layers' weights and the head."""
    sparse, linear = _layers(dm)
    return (sparse * _mixer_params(dm, "attn")
            + linear * _mixer_params(dm, "linear")
            + dm["layers"] * _mlp(dm) + dm["width"] * dm["vocab"])


def compressed(dm: dict, context: int) -> int:
    """Compressed keys complete at a context of `context` positions."""
    kernel, stride = dm["select"][:2]
    return max(context - kernel + stride, 0) // stride


def attended(dm: dict, context: int) -> int:
    """Keys one sparse layer's token attends over at that context."""
    _, _, block, topk, init, window, dense = dm["select"]
    if context < dense:
        return context
    return min(context, block * (init + topk) + window)


def _token_mixing(dm: dict, context: int) -> int:
    """Multiply-adds of one token's mixers, weights apart: a sparse
    layer's compressed scores (one product a query head) and its
    attention (score and value), a linear layer's state read and
    update."""
    sparse, linear = _layers(dm)
    h, hd = dm["heads"], dm["head_dim"]
    return (sparse * h * hd * (compressed(dm, context)
                               + 2 * attended(dm, context))
            + linear * 2 * h * hd * hd)


def matmul_shapes(dm: dict) -> list[tuple[int, int, int]]:
    """(din, dout, calls per forward) of every weight matmul that
    `qmatmul` dispatches."""
    w, hd, h, kv = dm["width"], dm["head_dim"], dm["heads"], dm["kv_heads"]
    sparse, linear = _layers(dm)
    return [(w, h * hd, sparse), (w, 2 * kv * hd, sparse),
            (w, 3 * h * hd, linear), (w, h * hd, dm["layers"]),
            (h * hd, w, dm["layers"]), (w, dm["mlp"], 2 * dm["layers"]),
            (dm["mlp"], w, dm["layers"]), (w, dm["vocab"], 1)]


def span_flops(dm: dict, start: int, n: int) -> int:
    """Model FLOPs of n consecutive tokens at positions start..start+n-1
    (contexts start+1 .. start+n)."""
    mixing = sum(_token_mixing(dm, c) for c in range(start + 1, start + n + 1))
    return int(2 * _outside(dm) * n + 2 * mixing)


def token_flops(dm: dict, context: int) -> int:
    return span_flops(dm, context - 1, 1)


def state_tick_work(dm: dict, *, contexts, kv_rows_read: int,
                    index_rows_read: int, weight_bytes: int = 2,
                    cache_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one decode tick: one token a decoding
    request, `contexts` each request's own context (its depth + 1);
    `kv_rows_read` the K/V rows the sparse layers' reads touched and
    `index_rows_read` the compressed keys their selections scored
    (a K/V head's each), both summed over the sparse layers: the
    tick's counters. Bytes: every weight once; a request's states read
    and written once a linear layer; the compressed keys scored and
    the K and V rows touched, once."""
    sparse, linear = _layers(dm)
    h, hd, kv = dm["heads"], dm["head_dim"], dm["kv_heads"]
    ops = 2 * _outside(dm) * len(contexts) + 2 * sum(
        _token_mixing(dm, c) for c in contexts)
    moved = (weight_bytes * _outside(dm)
             + len(contexts) * linear * 2 * STATE_BYTES * h * hd * hd
             + cache_bytes * hd * index_rows_read
             + cache_bytes * 2 * kv * hd * kv_rows_read)
    return ops, moved


def state_tick_least_seconds(dm: dict, peaks: dict, **tick) -> float:
    """The least time the chip could take for that tick: the larger of
    operations over the bf16 peak and bytes over the HBM peak."""
    ops, moved = state_tick_work(dm, **tick)
    return max(ops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])


def check() -> None:
    """Against counts written out by hand at one small shape."""
    dm = {"width": 8, "heads": 4, "kv_heads": 2, "head_dim": 3, "mlp": 5,
          "layers": 4, "mixers": ("attn", "linear", "linear", "attn"),
          "select": (4, 2, 8, 2, 1, 16, 32), "vocab": 10}
    # A sparse layer's projections: q 8x12, k and v 8x6 each, gate 8x12,
    # out 12x8 = 384; a linear layer's: q, k, v 8x12 each, gate, out = 480.
    assert _mixer_params(dm, "attn") == 96 + 48 + 48 + 96 + 96 == 384
    assert _mixer_params(dm, "linear") == 3 * 96 + 96 + 96 == 480
    assert _mlp(dm) == 120
    assert _outside(dm) == 2 * 384 + 2 * 480 + 4 * 120 + 80 == 2288
    # Kernel 4, stride 2: contexts 3, 4, 5, 6 hold 0, 1, 1, 2 compressed keys.
    assert [compressed(dm, c) for c in (3, 4, 5, 6)] == [0, 1, 1, 2]
    # Under dense_len 32 every key; past it 8 x (1 + 2) + 16 = 40.
    assert [attended(dm, c) for c in (5, 31, 32, 100)] == [5, 31, 32, 40]
    # One token at context 100: 2 sparse layers x 12 x (49 + 2 x 40), 2
    # linear layers x 2 x 4 x 9.
    assert _token_mixing(dm, 100) == 2 * 12 * 129 + 2 * 72 == 3240
    assert token_flops(dm, 100) == 2 * 2288 + 2 * 3240
    assert span_flops(dm, 98, 2) == token_flops(dm, 99) + token_flops(dm, 100)
    assert sum(a * b * n for a, b, n in matmul_shapes(dm)) == 2288
    # A tick of requests at contexts 5 and 100, whose reads touched 90
    # K/V rows and scored 102 compressed keys, bf16 weights and cache:
    ops, moved = state_tick_work(dm, contexts=[5, 100], kv_rows_read=90,
                                 index_rows_read=102)
    assert ops == 2 * 2288 * 2 + 2 * (2 * 12 * (1 + 10) + 144 + 3240)
    assert moved == (2 * 2288 + 2 * 2 * 2 * 4 * 4 * 9 + 2 * 3 * 102
                     + 2 * 2 * 2 * 3 * 90)
    assert state_tick_least_seconds(
        dm, {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e2}, contexts=[5, 100],
        kv_rows_read=90, index_rows_read=102) == max(ops / 1e3, moved / 1e2)
