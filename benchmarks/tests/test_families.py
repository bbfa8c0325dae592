"""A configuration names its family, and everything that knows the
shape of a block is found under families/<family>/ by that name
(README, "families").

1. Every configuration of BENCHMARK.json and of tests/tiny names a
   family whose four modules load and give the interface.
2. The GPT-2 family, after its move out of benchmarks/*.py, gives the
   numbers that record_golden.py took from the commit before the move
   (testdata/families.golden.json): weights and reference to 1e-6, the
   counts of operations exactly.
3. tests/tiny's own family is found under tests/tiny/families/, and the
   shared readers count through it (its `dims` has none of the first
   family's names).
4. A configuration with no family, or one that is not there, ends the
   run with the list of those there are, before the chip is claimed.

The run of the second family's cell, its control and its faults are
test_correct.py's (its `CELLS`).
"""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import run, work  # noqa: E402
from benchmarks.tests import record_golden  # noqa: E402

BENCH = ROOT / "benchmarks"
TINY = BENCH / "tests" / "tiny"
GOLDEN = json.loads((BENCH / "testdata" / "families.golden.json").read_text())


def configs_of(bench_file: Path):
    bench = json.loads(bench_file.read_text())
    base = bench_file.parent
    return [(base / bench["paths"][0], base / c["file"])
            for c in bench["configs"]]


CONFIGS = configs_of(ROOT / "BENCHMARK.json") + configs_of(TINY / "bench.json")


def family_of(bench_dir: Path, config_file: Path):
    cfg = json.loads(config_file.read_text())
    return cfg, run.load_family(run.family_dir(cfg, bench_dir))


@pytest.mark.parametrize("bench_dir,config_file", CONFIGS,
                         ids=[c.stem for _, c in CONFIGS])
def test_configuration_names_a_family_with_the_interface(bench_dir,
                                                         config_file):
    cfg, fam = family_of(bench_dir, config_file)
    assert fam.name == cfg["family"]
    dm = fam.weights.dims(cfg)
    # What shared code reads of `dims`, whatever else a family keeps there.
    assert dm["vocab"] > 0 and dm["max_seq"] >= int(cfg["max_len"])
    for module, names in {
            "build": ("serving_params", "engine_of"),
            "reference": ("forward_logits",),
            "work": ("span_flops", "token_flops", "matmul_shapes",
                     "check")}.items():
        for name in names:
            assert callable(getattr(getattr(fam, module), name)), name
    fam.work.check()
    assert fam.work.span_flops(dm, 3, 2) == (
        fam.work.token_flops(dm, 4) + fam.work.token_flops(dm, 5))
    assert all(len(s) == 3 for s in fam.work.matmul_shapes(dm))


def same(got, want, path=""):
    """Two records of record_golden.py: the same keys and shapes, every
    float within 1e-6 (absolute and relative), every int equal."""
    if isinstance(want, dict):
        assert sorted(got) == sorted(want), path
        for k in want:
            same(got[k], want[k], f"{path}/{k}")
    elif isinstance(want, list):
        assert len(got) == len(want), path
        for i, (g, w) in enumerate(zip(got, want)):
            same(g, w, f"{path}[{i}]")
    elif isinstance(want, float):
        assert got == pytest.approx(want, rel=1e-6, abs=1e-6), path
    else:
        assert got == want, path


@pytest.mark.parametrize("name", sorted(GOLDEN["tiny"]))
def test_gpt2_weights_and_reference_as_before_the_move(name):
    cfg, fam = family_of(TINY, TINY / "configs" / f"{name}.json")
    same(record_golden.tiny_record(fam.weights,
                                   fam.reference.forward_logits, cfg),
         GOLDEN["tiny"][name])


@pytest.mark.parametrize("name", sorted(GOLDEN["work"]))
def test_gpt2_work_counts_as_before_the_move(name):
    cfg, fam = family_of(BENCH, BENCH / "configs" / f"{name}.json")
    peaks = record_golden.v5e_peaks()
    got = record_golden.work_record(
        fam.weights.dims(cfg), fam.work,
        lambda rows, dm: work.int8_gemv_least_seconds(
            rows, fam.work.matmul_shapes(dm), peaks))
    assert got == GOLDEN["work"][name]       # to the last digit


def test_second_family_is_found_under_tests_tiny_and_readers_count_by_it():
    cfg, fam = family_of(TINY, TINY / "configs" / "tiny-gqa.json")
    assert fam.dir == (TINY / "families" / "rope_gqa").resolve()
    assert {Path(getattr(fam, m).__file__).parent for m in run.FAMILY_MODULES
            } == {fam.dir}
    # The real benchmark cannot name it; tests/tiny borrows the first.
    assert "rope_gqa" not in run.families_in(BENCH)
    assert run.families_in(TINY)["gpt2"] == BENCH / "families" / "gpt2"
    dm = fam.weights.dims(cfg)
    assert not {"d", "hd", "n_kv", "ffn", "heads", "depth"} & set(dm)
    # serve_step_mfu through this family: one request, a 5-token chunk
    # at depth 0, then one decoded token at depth 5.
    ticks = [{"prefill": [0, 7, 5], "decoded": [], "finished": [],
              "preempted": [], "aborted": []},
             {"prefill": [], "decoded": [[0, 7]], "finished": [7],
              "preempted": [], "aborted": []}]
    mfu = run.load_named(TINY, "layer_metrics", "serve_step_mfu").read({
        "dims": dm, "family": fam, "ticks": ticks, "window_s": 2.0,
        "chips": 1, "peaks": {"bf16_flops": 1e9}})
    flops = fam.work.span_flops(dm, 0, 5) + fam.work.token_flops(dm, 6)
    assert mfu == pytest.approx(100.0 * flops / 2e9)
    # ... and nothing of the GEMV roofline where the weights are bf16.
    assert run.load_named(TINY, "layer_metrics", "int8_gemv_roofline").read(
        {"config": cfg}) is None


@pytest.mark.parametrize("family", [None, "gpt3"], ids=["none", "unknown"])
def test_no_such_family_ends_the_run_before_the_chip(family, tmp_path,
                                                     monkeypatch):
    shutil.copytree(TINY, tmp_path / "tiny")
    file = tmp_path / "tiny" / "configs" / "tiny-mha.json"
    cfg = json.loads(file.read_text())
    del cfg["family"]
    if family:
        cfg["family"] = family
    file.write_text(json.dumps(cfg))

    def no_chip_yet(*a, **kw):
        raise AssertionError("the chip was claimed")

    monkeypatch.setattr(run, "claim_chip", no_chip_yet)
    with pytest.raises(SystemExit) as e:
        run.run_cell("tiny-mha.mix", seed=1, seconds=1, trace=False,
                     bench_file=tmp_path / "tiny" / "bench.json",
                     require_chip=False)
    assert "['gpt2', 'rope_gqa']" in str(e.value)
    assert repr(family) in str(e.value)
