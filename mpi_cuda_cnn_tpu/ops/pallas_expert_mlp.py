"""The gated expert MLP over sorted (token, expert) pairs: each touched
expert's matrices streamed about once.

parallel/ep.moe_held_inference sorts a forward's pairs by held expert.
Where many land on this chip, this kernel takes the sorted rows in row
tiles and visits every (row tile, expert) pair that meets — a tile
that lies inside one expert's rows once, a tile that straddles k
experts k times: at most tiles + experts hit - 1 visits. One grid step
a visit: the visit's expert, read off a prefetched vector, picks the
step's three blocks, the expert's whole `wg`, `wu` (dim, width) and
`wd` (width, dim). Consecutive visits of one expert keep its blocks
(the pipeline fetches a block only when its index changes), so an
expert's 3 x dim x width weights are streamed ONCE a layer however its
rows fall, and the next expert's fetch runs under this visit's
products. A visit computes down(act(x wg) * (x wu)) for the whole tile
and keeps the rows that are the expert's: bf16 (the rows' dtype) into
the MXU, f32 accumulation, the hidden rows rounded to the rows' dtype
before `wd` — the arithmetic of the walk's three lax.ragged_dot calls
a step. Rows past the last expert's end are never written.

The grid is as long as the visits there are (a traced scalar), so a
forward with few valid rows pays for the experts it touches and
nothing else. Interpret mode (platform cpu) runs the same body.

Measured on the v5e against XLA's grouped kernel at larger steps,
jax's megablox.gmm and a layout that gives every expert tiles of its
own: PERF.md section 6, PR 33.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.backend import pallas_interpret

# Rows a tile. 64, 128 and 256 read the same to 1.5% at 3,072 pairs
# over 64 experts (the visits' products hide under the next expert's
# fetch); 128 is the walk's step, so both forms share one padding.
ROW_TILE = 128

# The scoped VMEM the kernel may take: an expert's three matrices twice
# (the pipeline's two buffers) beside a tile's rows and its f32 output.
# The v5e has 128 MiB; the default scope of 16 MiB holds no 2 x 11.8 MB.
_VMEM_LIMIT = 100 * 2**20


def fits(dim: int, width: int, itemsize: int) -> bool:
    """Whether an expert's three matrices fit the kernel's VMEM twice
    over with room for a tile's rows: the kernel takes whole matrices
    as blocks and has no other form."""
    return 2 * 3 * dim * width * itemsize <= _VMEM_LIMIT * 3 // 4


def visits(sizes, rows: int, tile: int = ROW_TILE):
    """The (row tile, expert) pairs that meet, in the rows' order.
    sizes: (held,) int32 rows an expert, sorted by expert, their sum <=
    `rows`. Returns (starts (held + 1,) int32, the row each expert's
    rows begin at and the last one's end; expert (most,) and row_tile
    (most,) int32 of each visit, past the last visit the last one
    again; count () int32 of visits), most = rows // tile + held - 1."""
    n = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    first = starts[:-1] // tile
    per = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 0)
    stop = jnp.cumsum(per)
    at = jnp.minimum(jnp.arange(rows // tile + n - 1, dtype=jnp.int32),
                     jnp.maximum(stop[-1] - 1, 0))
    expert = jnp.minimum(jnp.searchsorted(stop, at, side="right"), n - 1)
    row_tile = first[expert] + at - (stop[expert] - per[expert])
    return (starts.astype(jnp.int32), expert.astype(jnp.int32),
            jnp.clip(row_tile, 0, rows // tile - 1).astype(jnp.int32),
            stop[-1].astype(jnp.int32))


def _kernel(starts, expert, row_tile, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
            *, act, tile):
    i = pl.program_id(0)
    e = expert[i]
    row = row_tile[i] * tile + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    x = x_ref[...]
    # bf16 products are exact in the MXU's one pass, and Mosaic takes no
    # other precision for them: an ambient "highest" (a test's, a
    # reference's) must not reach these dots.
    f32 = dict(preferred_element_type=jnp.float32,
               precision=(lax.Precision.DEFAULT if x.dtype == jnp.bfloat16
                          else None))
    h = act(jnp.dot(x, wg_ref[...], **f32)) * jnp.dot(x, wu_ref[...], **f32)
    y = jnp.dot(h.astype(x.dtype), wd_ref[...], **f32)
    # The tile's output block stays while its visits follow each other:
    # each keeps what earlier experts wrote outside its own rows.
    o_ref[...] = jnp.where((row >= starts[e]) & (row < starts[e + 1]),
                           y, o_ref[...])


@functools.partial(jax.jit, static_argnames=("act", "tile", "interpret"))
def _run(xs, sizes, wg, wu, wd, *, act, tile, interpret):
    (rows, dim), width = xs.shape, wg.shape[-1]
    starts, expert, row_tile, count = visits(sizes, rows, tile)

    def of_expert(i, starts, expert, row_tile):
        return expert[i], 0, 0

    def of_tile(i, starts, expert, row_tile):
        return row_tile[i], 0

    return pl.pallas_call(
        functools.partial(_kernel, act=act, tile=tile),
        out_shape=jax.ShapeDtypeStruct((rows, dim), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(count,),
            in_specs=[
                pl.BlockSpec((tile, dim), of_tile),
                pl.BlockSpec((None, dim, width), of_expert),
                pl.BlockSpec((None, dim, width), of_expert),
                pl.BlockSpec((None, width, dim), of_expert),
            ],
            out_specs=pl.BlockSpec((tile, dim), of_tile),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="expert_mlp",
    )(starts, expert, row_tile, xs, wg, wu, wd)


def expert_mlp(xs, sizes, bank: dict, act, *, tile: int = ROW_TILE):
    """down(act(xs wg) * (xs wu)) a row, each row through the expert
    whose rows it lies in. xs: (rows, dim) sorted by expert, rows a
    multiple of `tile`; sizes: (held,) int32 rows an expert; bank: {wg,
    wu: (held, dim, width), wd: (held, width, dim)} in xs' dtype; act:
    the gate branch's activation. Returns (rows, dim) f32; rows past
    the experts' end hold whatever was there."""
    return _run(xs, sizes, bank["wg"], bank["wu"], bank["wd"], act=act,
                tile=tile, interpret=pallas_interpret())
