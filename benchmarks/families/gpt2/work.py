"""Operations this block needs, from shapes alone: what
`serve_step_mfu` counts per token and which weight matmuls a kernel's
roofline has to account for (the kernel's own operations and bytes:
benchmarks/work.py).

`dm` is weights.dims(configuration). Counted is what the mathematics
needs — a multiply-add is 2 operations, attention reaches back over
each token's own context and not over the padded table, recomputation
and padding count for nothing.
"""

from __future__ import annotations


def matmul_shapes(dm: dict) -> list[tuple[int, int, int]]:
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
    return sum(din * dout * n for din, dout, n in matmul_shapes(dm))


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


def check() -> None:
    """The counts above against one written out by hand at a small
    shape (check_benchmark.py runs every family's)."""
    dm = {"d": 8, "heads": 2, "hd": 4, "n_kv": 1, "depth": 3, "ffn": 32,
          "vocab": 10}
    # wq 8x8, wkv 8x8, wo 8x8, w1 8x32, w2 32x8 per layer; head 8x10.
    assert matmul_params(dm) == 3 * (64 + 64 + 64 + 256 + 256) + 80
    # 2 tokens at positions 5, 6: contexts 6 and 7.
    want = 2 * 2 * matmul_params(dm) + 4 * 3 * 2 * 4 * (6 + 7)
    assert span_flops(dm, 5, 2) == want
    assert token_flops(dm, 6) + token_flops(dm, 7) == want
