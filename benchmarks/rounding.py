"""The control's rounding: a tensor taken to the precision below the
configuration's and back to f32. Every family's reference applies it to
ITS weight matrices and ITS cached tensors (README, "correct"), so that
one definition of "int4" and "fp8" judges every cell's control.

"int4": absmax over `axis` (per output channel for a weight; per
position and head for a cached tensor) over 7 levels a side. "fp8": the
same scaling, to e4m3's 3 bits of mantissa.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def round_to(x, lower: str, axis: int):
    """x rounded to the lower precision, back in f32."""
    if lower == "int4":
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 7.0,
                        1e-10)
        return jnp.clip(jnp.round(x / s), -7, 7) * s
    if lower == "fp8":
        # 4 exponent bits, 3 of mantissa (largest finite 240, as IEEE
        # would have e4m3), scaled by absmax like the int forms.
        # reduce_precision is an operation of its own in the HLO; a cast
        # there and back is one XLA may drop ("excess precision"), and
        # on the chip it did (PR 24).
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 240.0,
                        1e-10)
        return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                        mantissa_bits=3) * s
    raise ValueError(f"lower precision {lower!r}: want int4 or fp8")
