"""Operations and bytes a KERNEL needs for one call, from its shapes
alone, and the least time a list of such calls could take on the chip.

Kept with the benchmark so that no later PR can move a utilisation by
recounting. What a BLOCK needs — operations per token, and which weight
matmuls one forward makes — is its family's
(`families/<family>/work.py`): a reader hands this file the family's
list. A multiply-add is 2 operations; padding counts for nothing.
"""

from __future__ import annotations


def int8_gemv_work(rows: int, din: int, dout: int) -> tuple[int, int]:
    """(operations, bytes) of one int8 GEMV call y = (x @ q) * s:
    x (rows, din) f32, q (din, dout) int8, s (1, dout) f32, y f32."""
    ops = 2 * rows * din * dout
    moved = din * dout + 4 * dout + 4 * rows * din + 4 * rows * dout
    return ops, moved


def int8_gemv_least_seconds(rows: int, shapes, peaks: dict) -> float:
    """Least time one whole forward's int8 GEMV calls could take on the
    chip at `rows` rows. `shapes`: the family's `work.matmul_shapes(dm)`,
    (din, dout, calls per forward) each. Per call the larger of
    operations over the bf16 peak (the activations are f32; bf16 is the
    fastest the MXU could take them) and bytes over the HBM peak."""
    total = 0.0
    for din, dout, n in shapes:
        ops, moved = int8_gemv_work(rows, din, dout)
        total += n * max(ops / peaks["bf16_flops"],
                         moved / peaks["hbm_bytes_per_s"])
    return total
