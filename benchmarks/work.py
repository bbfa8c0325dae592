"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no later PR can move a utilisation by
recounting. `dm` is weights.dims(configuration). Counted is what the
mathematics needs — a multiply-add is 2 operations, attention reaches
back over each token's own context and not over the padded table,
recomputation and padding count for nothing.
"""

from __future__ import annotations


def gemv_shapes(dm: dict) -> list[tuple[int, int, int]]:
    """(din, dout, calls per forward) of every weight matmul that
    `qmatmul` dispatches: the block's projections and MLP, and the head."""
    d, ffn, depth = dm["d"], dm["ffn"], dm["depth"]
    if dm["n_kv"] == dm["heads"]:
        attn = [(d, 3 * d, depth)]
    else:
        attn = [(d, d, depth), (d, 2 * dm["n_kv"] * dm["hd"], depth)]
    return attn + [(d, d, depth), (d, ffn, depth), (ffn, d, depth),
                   (d, dm["vocab"], 1)]


def matmul_params(dm: dict) -> int:
    return sum(din * dout * n for din, dout, n in gemv_shapes(dm))


def token_flops(dm: dict, context: int) -> int:
    """Model FLOPs of one token that attends to `context` keys (itself
    included): 2 per matmul parameter, and QK^T plus PV over its
    context in every layer."""
    attn = 4 * dm["depth"] * dm["heads"] * dm["hd"] * context
    return 2 * matmul_params(dm) + attn


def span_flops(dm: dict, start: int, n: int) -> int:
    """Model FLOPs of n consecutive tokens at positions start..start+n-1
    (a prefill chunk, or n=1 for a decoded token): contexts start+1 ..
    start+n."""
    contexts = n * start + n * (n + 1) // 2
    return (2 * matmul_params(dm) * n
            + 4 * dm["depth"] * dm["heads"] * dm["hd"] * contexts)


def int8_gemv_work(rows: int, din: int, dout: int) -> tuple[int, int]:
    """(operations, bytes) of one int8 GEMV call y = (x @ q) * s:
    x (rows, din) f32, q (din, dout) int8, s (1, dout) f32, y f32."""
    ops = 2 * rows * din * dout
    moved = din * dout + 4 * dout + 4 * rows * din + 4 * rows * dout
    return ops, moved


def int8_gemv_least_seconds(rows: int, dm: dict, peaks: dict) -> float:
    """Least time one whole forward's int8 GEMV calls could take on the
    chip at `rows` rows: per call the larger of operations over the
    bf16 peak (the activations are f32; bf16 is the fastest the MXU
    could take them) and bytes over the HBM peak."""
    total = 0.0
    for din, dout, n in gemv_shapes(dm):
        ops, moved = int8_gemv_work(rows, din, dout)
        total += n * max(ops / peaks["bf16_flops"],
                         moved / peaks["hbm_bytes_per_s"])
    return total
