"""The `window_moe` family's tiny benchmark (tests/tiny_window: its own
bench.json, one configuration, one cell; the family itself is
benchmarks/families/window_moe, found as the real benchmark finds it):
the program correct and the fp8 control not; four kept faults, each not
correct through run.py's own comparison — a decode token altered and a
tick that misses its newest row (test_correct.py's), a windowed layer
that reads one block too few at the window's far edge, and one that
reads the rows of pages it gave back; and the three readers this family
brings, on records written out by hand and on a recorded tiny run.

The cell's limits were read on the CPU as the real cell's were on the
chip (its file's `limits_from`). The two window faults are functions of
this file so that a chip run at the real cell's size can apply the same
ones (`window_block_short`, `reads_freed_rows`).
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402
from benchmarks.tests import test_correct  # noqa: E402

BENCH = ROOT / "benchmarks"
TINY_WINDOW = Path(__file__).resolve().parent / "tiny_window" / "bench.json"
CELL = "tiny-window.mix"
SEED = 2**31 + 32


def run_tiny(**kw):
    return run.run_cell(CELL, seed=SEED, seconds=3, trace=False,
                        bench_file=TINY_WINDOW, require_chip=False, **kw)


# -- this PR's own two faults -------------------------------------------------

def _retrace_reads():
    """The bounded read is jitted by itself and keeps its traces by
    shape: drop them before a fault (and after it: `fresh_traces`)."""
    from mpi_cuda_cnn_tpu.serve import paged_cache

    paged_cache.bounded_read.clear_cache()


def window_block_short(patch, block: int = 16):
    """A windowed layer's read starts one block of `block` keys too
    late: the keys of the block that holds the window's far edge are
    not seen (unless that is the query's own block). `patch` is a
    pytest MonkeyPatch."""
    import jax.numpy as jnp

    from mpi_cuda_cnn_tpu.serve import paged_cache

    real = paged_cache.causal_mask

    def short(keys, queries, window=0):
        seen = real(keys, queries, window)
        if window:
            edge = (jnp.maximum(queries - window + 1, 0) // block + 1) * block
            seen = seen & (keys >= jnp.minimum(edge, queries // block * block))
        return seen

    _retrace_reads()
    patch.setattr(paged_cache, "causal_mask", short)


def reads_freed_rows(patch):
    """A windowed layer reads every row up to the query's, through a
    table whose entries behind the window went back to scratch: the
    rows of pages it gave back."""
    from mpi_cuda_cnn_tpu.serve import paged_cache

    real = paged_cache.paged_update_attend

    def forgetful(c, q, k, v, positions, valid, block_table, page_size,
                  window=0):
        return real(c, q, k, v, positions, valid, block_table, page_size)

    _retrace_reads()
    patch.setattr(paged_cache, "paged_update_attend", forgetful)


@pytest.fixture(autouse=True)
def fresh_traces():
    yield
    from mpi_cuda_cnn_tpu.serve import paged_cache

    paged_cache.bounded_read.clear_cache()


def test_family_is_the_real_benchmarks_own():
    cfg = run.load_cell(CELL, TINY_WINDOW)[1]
    there = run.families_in(TINY_WINDOW.parent)
    assert there[cfg["family"]] == ROOT / "benchmarks/families/window_moe"
    assert cfg["prefill_chunk"] == 16 and cfg["sliding_window_size"] == 32


def test_program_correct_control_not():
    line = run_tiny(lower="fp8")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    control = line["control"]
    assert control["correct"] is False, control
    # ... by the mean gap: at this size the largest gap cannot part the
    # two (the cell file's `limits_from`).
    gap_mean = control["compared"]["gap_mean"]
    assert gap_mean["value"] > 2 * gap_mean["limit"], control
    assert set(line["metrics"]) == {"tokens_per_s", "tpot_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", [test_correct._alter_decode,
                                   test_correct._stale_cache_row,
                                   window_block_short, reads_freed_rows])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    line = run_tiny()
    assert line["correct"] is False, line["compared"]
    over = [k for k, v in line["compared"].items()
            if "limit" in v and v["value"] > v["limit"]]
    assert "gap_mean" in over, line["compared"]


# -- the three readers --------------------------------------------------------

class FakeTrace:
    def __init__(self, tick_runs):
        self.runs = tick_runs

    def module_durations(self, name):
        return self.runs if name == "jit_tick" else []


def reader(name):
    return run.load_named(BENCH, "layer_metrics", name).read


def test_readers_on_records_written_out_by_hand():
    fam = run.load_family(BENCH / "families" / "window_moe")
    cfg = run.load_cell(CELL, TINY_WINDOW)[1]
    dm = fam.weights.dims(cfg)
    assert (dm["window"], sum(dm["window_layout"]), dm["layers"]) == (32, 6, 8)
    tick = {"prefill": [], "decoded": [], "finished": [], "preempted": [],
            "aborted": []}
    moe = {"moe_assignments": 24, "moe_experts_hit": 20, "moe_load_max": 2}
    ticks = [
        # Request 7 prefills 40 rows: two table pages of 16 and a third.
        {**tick, "prefill": [0, 7, 40], "pages_held": [3, 3],
         "window_pages_freed": 0},
        # It decodes at depth 40 (a windowed layer holds 32 of its 41
        # rows); the read touched 6 x 48 windowed rows.
        {**tick, "decoded": [[0, 7]], **moe, "kv_rows_read": 400,
         "kv_rows_read_window": 288, "pages_held": [3, 3],
         "window_pages_freed": 0},
        # Request 8 prefills 5 rows whole and decodes in the same
        # iteration at depth 5 (6 rows); 7 at depth 41, and its first
        # windowed page went back.
        {**tick, "prefill": [1, 8, 5, "emit"], "decoded": [[0, 7], [1, 8]],
         **moe, "kv_rows_read": 700, "kv_rows_read_window": 456,
         "pages_held": [4, 3], "window_pages_freed": 1},
    ]
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    ctx = {"ticks": ticks, "dims": dm, "family": fam, "config": cfg,
           "peaks": peaks, "first_traced": 2,
           "trace": FakeTrace([1e-3, 2e-4])}
    # Only the last record is in the traced slice.
    assert reader("window_rows_read_ratio")(ctx) == pytest.approx(
        456 / (6 * (32 + 6)))
    assert reader("window_rows_read_ratio")(
        {**ctx, "first_traced": 1}) == pytest.approx(
        (288 + 456) / (6 * (32 + 32 + 6)))
    # After record 0: depth 40 = 3 pages; after 1: 41 = 3; after 2: 42
    # and 6: 3 + 1. The windowed group held 3, 3, 3.
    assert reader("window_pages_held_share")(ctx) == pytest.approx(
        100 * (3 / 3 + 3 / 3 + 3 / 4) / 3)
    least = fam.work.window_tick_least_seconds(
        dm, peaks, contexts=[42, 6], assignments=24, experts_hit=20,
        weight_bytes=2, cache_bytes=2)
    assert reader("tick_roofline.window")(ctx) == pytest.approx(
        100 * least / 2e-4)
    assert 0 < reader("tick_roofline.window")(ctx) < 100
    # The accepted tick_roofline finds nothing to read for this family.
    assert reader("tick_roofline")(ctx) is None
    # A program (or a family) without the counters: nothing, no raise.
    bare = [{k: v for k, v in t.items()
             if not k.startswith(("moe", "kv_", "pages_", "window_"))}
            for t in ticks]
    for name in ("window_rows_read_ratio", "window_pages_held_share",
                 "tick_roofline.window"):
        assert reader(name)({**ctx, "ticks": bare}) is None
    gpt2 = run.load_family(BENCH / "families" / "gpt2")
    assert reader("tick_roofline.window")({**ctx, "family": gpt2}) is None


def test_readers_on_a_recorded_tiny_run():
    cell = run.prepare(CELL, seed=SEED, bench_file=TINY_WINDOW,
                       require_chip=False)
    requests = run.make_requests(cell["workload"], cell["bench_dir"],
                                 seed=SEED, seconds=3.0,
                                 vocab=cell["dims"]["vocab"])
    ticks = []
    cell["engine"].run(requests, mode="continuous", tick_sink=ticks.append)
    decoded = [t for t in ticks if t["decoded"]]
    assert decoded and all("kv_rows_read_window" in t for t in decoded)
    ctx = {"ticks": ticks, "dims": cell["dims"], "family": cell["family"],
           "config": cell["config"], "first_traced": len(ticks) // 2,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": FakeTrace([1e-3] * len(decoded))}
    # Tables this small are read whole (4 slots x 160 rows a layer), so
    # the reads touch far more than the windows hold ...
    assert reader("window_rows_read_ratio")(ctx) > 1.0
    # ... and the windowed group holds, of what one table would pin,
    # less the deeper the requests are past window + chunk.
    share = reader("window_pages_held_share")(ctx)
    assert 20 < share < 120, share
    assert sum(t["window_pages_freed"] for t in ticks) > 0
    assert 0 < reader("tick_roofline.window")(ctx) < 100
