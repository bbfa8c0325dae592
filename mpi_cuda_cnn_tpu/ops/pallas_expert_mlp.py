"""The gated expert MLP over sorted (token, expert) pairs: each touched
expert's matrices streamed about once.

parallel/ep.moe_held_inference sorts a forward's pairs by held expert,
a chunk's or a tick's, and this kernel takes the sorted rows in row
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

Where an expert's three matrices do not fit the VMEM twice over
(Command A+'s 3 x (4096, 4096) bf16, 96 MiB) they are taken in WIDTH
SLICES: a step's blocks are `wg[e][:, s]`, `wu[e][:, s]` (dim, tn) and
`wd[e][s, :]` (tn, dim), and act(x wg_s) * (x wu_s), rounded, times
wd_s is added into an f32 accumulator; gate and up act on each column
alone, so the slices' sum is the whole product. Every step's blocks
differ from the last, so here a visit streams its expert once, and the
visits are the expert's own: WINDOWS of `tile` rows from its first row
(rounded down to 16, a bf16 tile's rows), as many as its rows need,
read from HBM by the kernel itself; at the last slice the window is
read back, the expert's rows merged in and written. An expert whose
rows fit one window is streamed once however the 128-row tiles fall
(the tiles' order would stream a straddling expert once a tile).

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

# Rows a sliced window begins on: a bf16 tile's rows (an f32 tile's 8
# divide it), so that the kernel's copies of rows start on a tile.
ALIGN = 16

# The scoped VMEM the kernel may take: an expert's three matrices (or
# three slices of them) twice, the pipeline's two buffers, beside a
# tile's rows, its f32 output and, sliced, its f32 accumulator.
# The v5e has 128 MiB; the default scope of 16 MiB holds no 2 x 11.8 MB.
_VMEM_LIMIT = 100 * 2**20


def fits(dim: int, width: int, itemsize: int) -> bool:
    """Whether an expert's three matrices fit the kernel's VMEM twice
    over with room for a tile's rows: then they are whole blocks."""
    return 2 * 3 * dim * width * itemsize <= _VMEM_LIMIT * 3 // 4


def width_slice(dim: int, width: int, itemsize: int) -> int:
    """The width of the blocks an expert's matrices are taken in:
    `width` where they fit whole (`fits`), else the widest multiple of
    128 lanes that divides `width` and whose three (dim, slice) blocks
    fit the same room twice over (1,024 of Command A+'s 4,096); 0 where
    none does."""
    if fits(dim, width, itemsize):
        return width
    most = min(width, _VMEM_LIMIT * 3 // 4 // (2 * 3 * dim * itemsize))
    return max((tn for tn in range(128, most + 1, 128) if width % tn == 0),
               default=0)


def windows(sizes, rows: int, tile: int = ROW_TILE):
    """Each expert's rows in windows of `tile` rows, in the rows'
    order: an expert's first window begins at its first row rounded
    down to ALIGN, the next ones `tile` rows on, as many as its rows
    need; a window past `rows` is moved back to end there. sizes:
    (held,) int32 rows an expert, sorted by expert, their sum <=
    `rows`. Returns (starts (held + 1,) int32 as `visits` gives them;
    expert (most,) and first (most,) int32, each window's expert and
    first row, past the last window the last one again; count () int32
    of windows), most = rows // tile + held + ceil(held x (ALIGN - 1)
    / tile)."""
    n = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    lo = starts[:-1] // ALIGN * ALIGN
    per = jnp.where(sizes > 0, (ends - lo + tile - 1) // tile, 0)
    stop = jnp.cumsum(per)
    most = rows // tile + n + -(-n * (ALIGN - 1) // tile)
    at = jnp.minimum(jnp.arange(most, dtype=jnp.int32),
                     jnp.maximum(stop[-1] - 1, 0))
    expert = jnp.minimum(jnp.searchsorted(stop, at, side="right"), n - 1)
    first = lo[expert] + (at - (stop[expert] - per[expert])) * tile
    return (starts.astype(jnp.int32), expert.astype(jnp.int32),
            jnp.minimum(first, rows - tile).astype(jnp.int32),
            stop[-1].astype(jnp.int32))


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


def _gated(x_ref, wg_ref, wu_ref, wd_ref, act):
    """down(act(x wg) * (x wu)) of the blocks, in f32."""
    x = x_ref[...]
    # bf16 products are exact in the MXU's one pass, and Mosaic takes no
    # other precision for them: an ambient "highest" (a test's, a
    # reference's) must not reach these dots.
    f32 = dict(preferred_element_type=jnp.float32,
               precision=(lax.Precision.DEFAULT if x.dtype == jnp.bfloat16
                          else None))
    h = act(jnp.dot(x, wg_ref[...], **f32)) * jnp.dot(x, wu_ref[...], **f32)
    return jnp.dot(h.astype(x.dtype), wd_ref[...], **f32)


def _kernel(starts, expert, row_tile, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
            *, act, tile):
    i = pl.program_id(0)
    e = expert[i]
    row = row_tile[i] * tile + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    y = _gated(x_ref, wg_ref, wu_ref, wd_ref, act)
    # The tile's output block stays while its visits follow each other:
    # each keeps what earlier experts wrote outside its own rows.
    o_ref[...] = jnp.where((row >= starts[e]) & (row < starts[e + 1]),
                           y, o_ref[...])


def _sliced_kernel(starts, expert, first, x_hbm, wg_ref, wu_ref, wd_ref,
                   o_hbm, x_ref, o_ref, acc_ref, sem, *, act, tile):
    i, s = pl.program_id(0), pl.program_id(1)
    last_visit, last_slice = pl.num_programs(0) - 1, pl.num_programs(1) - 1
    slot = i % 2

    # The kernel's own copies of rows, each started a step or more before
    # it is waited for: the weights' blocks (the pipeline's) are queued
    # before them, and a copy waited for at once would wait for those.
    def rows_in(j, k):                 # window j's rows into x slot k
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(pl.multiple_of(first[j], ALIGN), tile)],
            x_ref.at[k], sem.at[k])

    def window(j, to_hbm):             # window j's output rows
        there = o_hbm.at[pl.ds(pl.multiple_of(first[j], ALIGN), tile)]
        return pltpu.make_async_copy(*((o_ref, there) if to_hbm
                                       else (there, o_ref)), sem.at[2])

    @pl.when((i == 0) & (s == 0))
    def _():
        rows_in(0, 0).start()

    @pl.when(s == 0)
    def _():
        rows_in(i, slot).wait()

        # The last window's rows must be in HBM before this one's are
        # read: two windows can share rows (a window runs past its
        # expert's end, and one moved back to end at the rows' end).
        @pl.when(i > 0)
        def _():
            window(i - 1, True).wait()

        window(i, False).start()

    @pl.when((s == last_slice) & (i < last_visit))
    def _():
        rows_in(i + 1, 1 - slot).start()

    y = _gated(x_ref.at[slot], wg_ref, wu_ref, wd_ref, act)

    @pl.when(s == 0)
    def _():
        acc_ref[...] = y

    @pl.when(s > 0)
    def _():
        acc_ref[...] += y

    # The window's rows as they are in HBM (earlier experts' among them),
    # the expert's own merged in, written once, at the last slice.
    @pl.when(s == last_slice)
    def _():
        window(i, False).wait()
        e = expert[i]
        row = first[i] + lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
        o_ref[...] = jnp.where((row >= starts[e]) & (row < starts[e + 1]),
                               acc_ref[...], o_ref[...])
        window(i, True).start()

        @pl.when(i == last_visit)
        def _():
            window(i, True).wait()


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


@functools.partial(jax.jit,
                   static_argnames=("act", "tile", "tn", "interpret"))
def _run_sliced(xs, sizes, wg, wu, wd, *, act, tile, tn, interpret):
    (rows, dim), width = xs.shape, wg.shape[-1]
    starts, expert, first, count = windows(sizes, rows, tile)

    def of_columns(i, s, starts, expert, first):
        return expert[i], 0, s

    def of_rows(i, s, starts, expert, first):
        return expert[i], s, 0

    return pl.pallas_call(
        functools.partial(_sliced_kernel, act=act, tile=tile),
        out_shape=jax.ShapeDtypeStruct((rows, dim), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(count, width // tn),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((None, dim, tn), of_columns),
                pl.BlockSpec((None, dim, tn), of_columns),
                pl.BlockSpec((None, tn, dim), of_rows),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((2, tile, dim), xs.dtype),
                            pltpu.VMEM((tile, dim), jnp.float32),
                            pltpu.VMEM((tile, dim), jnp.float32),
                            pltpu.SemaphoreType.DMA((3,))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="expert_mlp",
    )(starts, expert, first, xs, wg, wu, wd)


def expert_mlp(xs, sizes, bank: dict, act, *, tile: int = ROW_TILE,
               slice_width: int | None = None):
    """down(act(xs wg) * (xs wu)) a row, each row through the expert
    whose rows it lies in. xs: (rows, dim) sorted by expert, rows a
    multiple of `tile`; sizes: (held,) int32 rows an expert; bank: {wg,
    wu: (held, dim, width), wd: (held, width, dim)} in xs' dtype; act:
    the gate branch's activation; slice_width: the width of an expert's
    blocks, `width_slice` of the bank's shapes where None (whole
    matrices where they fit). Returns (rows, dim) f32; rows past the
    experts' end hold whatever was there."""
    wg = bank["wg"]
    width = wg.shape[2]
    tn = slice_width or width_slice(wg.shape[1], width, wg.dtype.itemsize)
    if not tn or width % tn:
        raise ValueError(f"no slice of width {tn} tiles experts {width} "
                         f"wide at dim {wg.shape[1]}")
    args = (xs, sizes, bank["wg"], bank["wu"], bank["wd"])
    if tn == width:
        return _run(*args, act=act, tile=tile, interpret=pallas_interpret())
    return _run_sliced(*args, act=act, tile=tile, tn=tn,
                       interpret=pallas_interpret())
