"""The `mla_moe` family's tiny benchmark (tests/tiny_mla: its own
bench.json, one configuration, one cell; the family itself is
benchmarks/families/mla_moe, found as the real benchmark finds it):
the program correct and the fp8 control not, and the faults of
test_correct.py — a decode token altered, a first token altered, a
tick that misses its newest latent row — each not correct.

The cell's limits were read on the CPU as the real cell's were on the
chip (its file's `limits_from`).
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402
from benchmarks.tests import test_correct  # noqa: E402

TINY_MLA = Path(__file__).resolve().parent / "tiny_mla" / "bench.json"
CELL = "tiny-mla.mix"


def run_tiny(**kw):
    return run.run_cell(CELL, seed=2**31 + 28, seconds=3, trace=False,
                        bench_file=TINY_MLA, require_chip=False, **kw)


def test_family_is_the_real_benchmarks_own():
    cfg = run.load_cell(CELL, TINY_MLA)[1]
    there = run.families_in(TINY_MLA.parent)
    assert there[cfg["family"]] == ROOT / "benchmarks/families/mla_moe"


def test_program_correct_control_not():
    line = run_tiny(lower="fp8")
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    control = line["control"]
    assert control["correct"] is False, control
    gap_mean = control["compared"]["gap_mean"]
    assert gap_mean["value"] > 2 * gap_mean["limit"], control


@pytest.mark.parametrize("fault", [test_correct._alter_decode,
                                   test_correct._alter_first_token,
                                   test_correct._stale_cache_row])
def test_fault_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    line = run_tiny()
    assert line["correct"] is False, line["compared"]
    over = [k for k, v in line["compared"].items()
            if "limit" in v and v["value"] > v["limit"]]
    assert "gap_mean" in over, line["compared"]
