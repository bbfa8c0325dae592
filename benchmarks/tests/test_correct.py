"""`correct` has to be able to come out false (README, "correct").

1. The control: the reference in the precision below the
   configuration's, put in the program's place, is not correct by the
   cell's own limits — while the program itself is.
2. Faults: the harness's look for a chip is skipped and the rest of a
   run is driven with the timed path broken underneath; `correct` comes
   out false. The faults a serving cell can have are a token altered
   where it is produced — in the decode tick, and in the prefill chunk
   that yields a request's first token — and a request that ends
   without its tokens.
3. Off the chip the command exits non-zero with nothing on stdout.

The sizes are tests/tiny's, small enough for the CPU; the limits in
its cell files were set there as the real cells' were on the chip
(program readings over seeds below, control above; tiny-gqa.mix, PR 27,
13 seeds on the CPU: program `gap_max` <= 0.036, `gap_mean` <= 0.00053,
the fp8 control >= 0.139 and >= 0.0075; limits 0.08 and 0.002).
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402

TINY = Path(__file__).resolve().parent / "tiny" / "bench.json"
# The third cell's configuration names a family that tests/tiny brings
# itself (tiny/families/rope_gqa): its control and faults are judged
# through a reference the GPT-2 family did not write.
CELLS = ["tiny-mqa.mix", "tiny-mha.mix", "tiny-gqa.mix"]


def run_tiny(cell, seed=2**31 + 11, **kw):
    return run.run_cell(cell, seed=seed, seconds=3, trace=False,
                        bench_file=TINY, require_chip=False, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_program_correct_control_not(cell):
    lower = run.load_cell(cell, TINY)[2]["correct"]["control"]
    line = run_tiny(cell, lower=lower)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    # The control goes through the same judge and limits, and fails:
    # the mean gap is the number a lower precision moves first.
    control = line["control"]
    assert control["correct"] is False, control
    gap_mean = control["compared"]["gap_mean"]
    assert gap_mean["value"] > 3 * gap_mean["limit"], control
    # ... and every end-to-end metric of the cell is there and not 0.
    bench = json.loads(TINY.read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())


def _alter_decode(monkeypatch):
    from mpi_cuda_cnn_tpu.serve.engine import PagedEngine

    real = PagedEngine.run_decode_tick

    def broken(self, dslots):
        nxt = np.array(real(self, dslots))
        nxt[dslots[0].idx] = (nxt[dslots[0].idx] + 1) % self.model.vocab
        return nxt

    monkeypatch.setattr(PagedEngine, "run_decode_tick", broken)


def _alter_first_token(monkeypatch):
    from mpi_cuda_cnn_tpu.serve.engine import PagedEngine

    real = PagedEngine.run_prefill_chunk

    def broken(self, slot):
        n, nxt = real(self, slot)
        return n, (nxt + 1) % self.model.vocab

    monkeypatch.setattr(PagedEngine, "run_prefill_chunk", broken)


def _stale_cache_row(monkeypatch):
    """The decode tick reads a cache that misses its newest row: every
    slot is told it sits one position earlier than it does."""
    from mpi_cuda_cnn_tpu.serve.engine import PagedEngine

    real = PagedEngine.run_decode_tick

    def broken(self, dslots):
        for s in dslots:
            s.cached -= 1
        try:
            return real(self, dslots)
        finally:
            for s in dslots:
                s.cached += 1

    monkeypatch.setattr(PagedEngine, "run_decode_tick", broken)


@pytest.mark.parametrize("fault", [_alter_decode, _alter_first_token,
                                   _stale_cache_row])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    line = run_tiny(cell)
    assert line["correct"] is False, line["compared"]
    over = [k for k, v in line["compared"].items()
            if "limit" in v and v["value"] > v["limit"]]
    assert "gap_mean" in over, line["compared"]


def test_short_answer_is_not_correct(monkeypatch):
    from mpi_cuda_cnn_tpu.serve.scheduler import Request

    monkeypatch.setattr(Request, "done", property(
        lambda self: len(self.out) >= max(1, self.max_new_tokens - 1)))
    line = run_tiny(CELLS[0])
    assert line["correct"] is False
    assert line["compared"]["short"]["value"] > 0


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "starcoderbase-7b.generation",
                   "--seed", "1", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "not a TPU" in out.err
