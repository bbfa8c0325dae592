"""The `sparse_linear` family's tiny benchmark (tests/tiny_sparse_linear:
its own bench.json, one configuration, one cell; the family itself is
benchmarks/families/sparse_linear, found as the real benchmark finds
it): the program correct and the fp8 control not; four kept faults,
each not correct through run.py's own comparison -- every block read
instead of the chosen ones, the lowest-scored blocks chosen, a state
not zeroed when a slot starts a request, a chunk that drops the state
the rows before it left (a fifth, a tick that writes no compressed
key, is defined here and held to the logits by tests/); and the two
readers this family brings, on records written out by hand and on
a recorded tiny run.

The cell's limits were read on the CPU as the real cell's are on the
chip (its file's `limits_from`). The faults are functions of this file
so that a chip run at the real cell's size applies the same ones
(tests/test_sparse_linear.py holds each of them to the logits too).
"""

import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402

BENCH = ROOT / "benchmarks"
TINY_SALA = Path(__file__).resolve().parent / "tiny_sparse_linear" / "bench.json"
CELL = "tiny-sala.mix"
SEED = 2**31 + 34


def run_tiny(**kw):
    return run.run_cell(CELL, seed=SEED, seconds=3, trace=False,
                        bench_file=TINY_SALA, require_chip=False, **kw)


# -- the kept faults ------------------------------------------------------------

def reads_every_block(patch):
    """A read of every block up to the query's: the model under
    `dense_len`, at every depth. `patch` is a pytest MonkeyPatch."""
    from mpi_cuda_cnn_tpu.serve import paged_cache

    real = paged_cache.select_blocks

    def every(q, kc, positions, valid, block_table, page_size, sel):
        return real(q, kc, positions, valid, block_table, page_size,
                    dataclasses.replace(sel, dense_len=1 << 30))

    patch.setattr(paged_cache, "select_blocks", every)


def takes_the_lowest_blocks(patch):
    """The `topk` LOWEST-scored of the far blocks, not the best."""
    import jax.numpy as jnp

    from mpi_cuda_cnn_tpu.serve import paged_cache

    real = paged_cache._best_blocks

    def lowest(far, k):     # the candidates' scores upside down
        return real(jnp.where(far >= 0, jnp.max(far, axis=-1, keepdims=True)
                              - far, -1.0), k)

    patch.setattr(paged_cache, "_best_blocks", lowest)


def state_not_zeroed(patch):
    """A slot's forward from position 0 continues whatever the slot's
    state held: the request before it, or the same one before it was
    preempted."""
    from mpi_cuda_cnn_tpu.serve import paged_cache

    patch.setattr(paged_cache, "_carried_state", lambda states, first: states)


def drops_the_carried_state(patch):
    """A forward reads its own rows' products only: the state the rows
    before it left adds nothing to its outputs (it is still updated)."""
    import jax.numpy as jnp

    from mpi_cuda_cnn_tpu.serve import paged_cache

    real = paged_cache.linear_attend

    def alone(q, k, v, state, valid, log_decay):
        o, _ = real(q, k, v, jnp.zeros_like(state), valid, log_decay)
        return o, real(q, k, v, state, valid, log_decay)[1]

    patch.setattr(paged_cache, "linear_attend", alone)


def stale_compressed_keys(patch):
    """A decode tick writes no compressed key: the keys that ticks
    complete stay whatever the page held."""
    from mpi_cuda_cnn_tpu.serve import paged_cache

    real = paged_cache._write_compressed

    def prefill_only(kc, kpool, positions, *rest):
        return kc if positions.shape[1] == 1 else real(
            kc, kpool, positions, *rest)

    patch.setattr(paged_cache, "_write_compressed", prefill_only)


FAULTS = [reads_every_block, takes_the_lowest_blocks, state_not_zeroed,
          drops_the_carried_state, stale_compressed_keys]


def test_family_is_the_real_benchmarks_own():
    cfg = run.load_cell(CELL, TINY_SALA)[1]
    there = run.families_in(TINY_SALA.parent)
    assert there[cfg["family"]] == ROOT / "benchmarks/families/sparse_linear"
    assert cfg["prefill_chunk"] == 16 and cfg["sparse_config"]["dense_len"] == 32


def test_program_correct_control_not():
    line = run_tiny(lower="fp8")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    control = line["control"]
    assert control["correct"] is False, control
    # ... by both limits, with room (the cell file's `limits_from`).
    for key in ("gap_max", "gap_mean"):
        row = control["compared"][key]
        assert row["value"] > 1.4 * row["limit"], control
    assert set(line["metrics"]) == {"tokens_per_s", "tpot_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", FAULTS[:4])
def test_fault_is_not_correct(fault, monkeypatch):
    """... by a limit of the gaps (every block read: by the largest
    gap alone; the others by the mean too). The fifth, a stale
    compressed key, moves nothing this cell can see -- answers of at
    most 48 tokens end before a key that a tick completed leaves the
    16-key window and is scored at all -- and is held to the logits
    in tests/test_sparse_linear.py, as the other four are too."""
    fault(monkeypatch)
    line = run_tiny()
    assert line["correct"] is False, line["compared"]
    over = [k for k, v in line["compared"].items()
            if "limit" in v and v["value"] > v["limit"]]
    assert set(over) & {"gap_max", "gap_mean"}, line["compared"]


# -- the two readers --------------------------------------------------------------

class FakeTrace:
    def __init__(self, tick_runs):
        self.runs = tick_runs

    def module_durations(self, name):
        return self.runs if name == "jit_tick" else []


def reader(name):
    return run.load_named(BENCH, "layer_metrics", name).read


def test_readers_on_records_written_out_by_hand():
    fam = run.load_family(BENCH / "families" / "sparse_linear")
    cfg = run.load_cell(CELL, TINY_SALA)[1]
    dm = fam.weights.dims(cfg)
    assert dm["mixers"] == ("attn", "linear", "linear", "linear")
    tick = {"prefill": [], "decoded": [], "finished": [], "preempted": [],
            "aborted": []}
    ticks = [
        # Request 7 prefills 40 rows.
        {**tick, "prefill": [0, 7, 40]},
        # It decodes at depth 40: 41 rows a read of every row would
        # touch in the one sparse layer; the walk touched 48.
        {**tick, "decoded": [[0, 7]], "kv_rows_read": 48,
         "index_rows_read": 19, "sparse_blocks_selected": 6,
         "state_slots_updated": 3},
        # Request 8 prefills 5 rows whole and decodes in the same
        # iteration at depth 5 (6 rows); 7 at depth 41 (42 rows).
        {**tick, "prefill": [1, 8, 5, "emit"], "decoded": [[0, 7], [1, 8]],
         "kv_rows_read": 56, "index_rows_read": 21,
         "sparse_blocks_selected": 7, "state_slots_updated": 6},
    ]
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    ctx = {"ticks": ticks, "dims": dm, "family": fam, "config": cfg,
           "peaks": peaks, "first_traced": 2,
           "trace": FakeTrace([1e-3, 2e-4])}
    # Only the last record is in the traced slice.
    assert reader("sparse_rows_read_share")(ctx) == pytest.approx(
        100 * 56 / (42 + 6))
    assert reader("sparse_rows_read_share")(
        {**ctx, "first_traced": 1}) == pytest.approx(
        100 * (48 + 56) / (41 + 42 + 6))
    least = fam.work.state_tick_least_seconds(
        dm, peaks, contexts=[42, 6], kv_rows_read=56, index_rows_read=21,
        weight_bytes=2, cache_bytes=2)
    assert reader("tick_roofline.state")(ctx) == pytest.approx(
        100 * least / 2e-4)
    assert 0 < reader("tick_roofline.state")(ctx) < 100
    # The accepted tick_roofline readers find nothing for this family.
    assert reader("tick_roofline")(ctx) is None
    assert reader("tick_roofline.window")(ctx) is None
    # A program (or a family) without the counters: nothing, no raise.
    bare = [{k: v for k, v in t.items()
             if not k.startswith(("kv_", "index_", "sparse_", "state_"))}
            for t in ticks]
    for name in ("sparse_rows_read_share", "tick_roofline.state"):
        assert reader(name)({**ctx, "ticks": bare}) is None
    gpt2 = run.load_family(BENCH / "families" / "gpt2")
    other = {**ctx, "family": gpt2, "dims": {"vocab": 8, "max_seq": 8}}
    assert reader("tick_roofline.state")(other) is None
    assert reader("sparse_rows_read_share")(other) is None


def test_readers_on_a_recorded_tiny_run():
    cell = run.prepare(CELL, seed=SEED, bench_file=TINY_SALA,
                       require_chip=False)
    requests = run.make_requests(cell["workload"], cell["bench_dir"],
                                 seed=SEED, seconds=3.0,
                                 vocab=cell["dims"]["vocab"])
    ticks = []
    cell["engine"].run(requests, mode="continuous", tick_sink=ticks.append)
    decoded = [t for t in ticks if t["decoded"]]
    assert decoded and all("index_rows_read" in t for t in decoded)
    ctx = {"ticks": ticks, "dims": cell["dims"], "family": cell["family"],
           "config": cell["config"], "first_traced": len(ticks) // 2,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": FakeTrace([1e-3] * len(decoded))}
    # Tables this small are read whole (4 slots x 160 rows), masked to
    # the chosen blocks: the read TOUCHES more than every live row.
    assert reader("sparse_rows_read_share")(ctx) > 100.0
    assert 0 < reader("tick_roofline.state")(ctx) < 100
    assert sum(t["state_resets"] for t in ticks) == len(
        [r for r in requests if r.admitted_at is not None])
