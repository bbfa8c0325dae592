"""Operations this family's block needs, from shapes alone. A
multiply-add is 2 operations; attention reaches back over each token's
own context, by every QUERY head (grouping saves cache bytes, not
operations); the rotation is elementwise and counts for nothing beside
the matmuls.
"""

from __future__ import annotations


def matmul_shapes(dm: dict) -> list[tuple[int, int, int]]:
    """(din, dout, calls per forward) of every weight matmul."""
    w, mlp, n = dm["width"], dm["mlp"], dm["layers"]
    return [(w, w, n), (w, 2 * dm["kv_heads"] * dm["head"], n), (w, w, n),
            (w, mlp, n), (mlp, w, n), (w, dm["vocab"], 1)]


def matmul_params(dm: dict) -> int:
    return sum(din * dout * n for din, dout, n in matmul_shapes(dm))


def span_flops(dm: dict, start: int, n: int) -> int:
    """Model FLOPs of n consecutive tokens at positions start..start+n-1:
    contexts start+1 .. start+n."""
    contexts = n * start + n * (n + 1) // 2
    return (2 * matmul_params(dm) * n
            + 4 * dm["layers"] * dm["q_heads"] * dm["head"] * contexts)


def token_flops(dm: dict, context: int) -> int:
    return span_flops(dm, context - 1, 1)


def check() -> None:
    """Against a count written out by hand at one small shape."""
    dm = {"width": 8, "q_heads": 4, "kv_heads": 2, "head": 2, "layers": 3,
          "mlp": 32, "vocab": 10}
    # wq 8x8, wkv 8x(2*2*2), wo 8x8, w1 8x32, w2 32x8 a layer; head 8x10.
    assert matmul_params(dm) == 3 * (64 + 64 + 64 + 256 + 256) + 80
    # 2 tokens at positions 5, 6: contexts 6 and 7, four query heads of 2.
    want = 2 * 2 * matmul_params(dm) + 4 * 3 * 4 * 2 * (6 + 7)
    assert span_flops(dm, 5, 2) == want
    assert token_flops(dm, 6) + token_flops(dm, 7) == want
