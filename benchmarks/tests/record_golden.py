"""Record, from a checkout of the commit BEFORE the block moved into a
family, the numbers test_families.py holds the moved code to:

    JAX_PLATFORMS=cpu python3 benchmarks/tests/record_golden.py <parent>

It imports that checkout's `benchmarks/weights.py`, `reference.py` and
`work.py` (the flat modules of commit 4b6d49f, PR 26) and writes
`benchmarks/testdata/families.golden.json` HERE. Run once, by the PR
that moved them (PR 27); kept so that a reader can see what the golden
file is and make it again from that commit. test_families.py takes the
same readings (`tiny_record`, `work_record`) of the family's modules.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
OUT = BENCH / "testdata" / "families.golden.json"
SEED = 2**31 + 27
BLOCK = 1                       # the block whose draws are recorded
TOKENS = 24                     # the fixed sequence's length
ROWS = [0, 7, 22, 23]
COLUMNS = [0, 1, 255, 511]      # logits kept entry by entry
LOWERS = (None, "int4", "fp8")
SPANS = [[0, 32], [160, 32], [165, 1], [1023, 1]]
GEMV_ROWS = [16, 32]


def tree_record(tree) -> dict:
    """Per leaf: shape, sum, sum of magnitudes, three entries."""
    import jax

    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        flat = np.asarray(leaf, np.float64).reshape(-1)
        at = [0, flat.size // 3, flat.size - 1]
        out[jax.tree_util.keystr(path)] = {
            "shape": list(leaf.shape), "sum": float(flat.sum()),
            "abs_sum": float(np.abs(flat).sum()),
            "entries": [float(flat[i]) for i in at]}
    return out


def tiny_record(weights, forward_logits, cfg: dict) -> dict:
    """One block's and the top's draws at SEED, and the reference's
    logits of a fixed sequence at ROWS, in f32 and both lower forms."""
    dm = weights.dims(cfg)
    key = weights.root_key(SEED)
    seq = ((np.arange(TOKENS) * 37 + 11) % dm["vocab"]).astype(np.int32)
    logits = forward_logits(dm, SEED, [seq], [np.asarray(ROWS, np.int32)],
                            LOWERS)
    return {
        "block_f32": tree_record(weights.block_f32(dm, key, BLOCK)),
        "top_f32": tree_record(weights.top_f32(dm, key)),
        "forward_logits": {
            str(lo): {"abs_sum": float(np.abs(np.asarray(
                          out[0], np.float64)).sum()),
                      "entries": np.asarray(out[0], np.float64)[
                          :, COLUMNS].tolist()}
            for lo, out in zip(LOWERS, logits)},
    }


def work_record(dm: dict, block_work, gemv_least_seconds) -> dict:
    """The counts at a real configuration's sizes;
    `gemv_least_seconds(rows, dm)` for the chip of peaks.json."""
    return {
        "matmul_params": block_work.matmul_params(dm),
        "span_flops": [[s, n, block_work.span_flops(dm, s, n)]
                       for s, n in SPANS],
        "token_flops": [[c, block_work.token_flops(dm, c)]
                        for c in (1, 1024)],
        "int8_gemv_least_seconds": [[r, gemv_least_seconds(r, dm)]
                                    for r in GEMV_ROWS],
    }


def v5e_peaks() -> dict:
    return json.loads((BENCH / "peaks.json").read_text())["TPU v5 lite"]


def main(parent: Path) -> None:
    sys.path.insert(0, str(parent))
    from benchmarks import reference, weights, work   # the parent's

    assert Path(weights.__file__).resolve().is_relative_to(parent.resolve())
    golden = {"recorded_from": "benchmarks/{weights,reference,work}.py at "
                               "commit 4b6d49f (PR 26), by "
                               "benchmarks/tests/record_golden.py",
              "tiny": {}, "work": {}}
    for name in ("tiny-mqa", "tiny-mha"):
        cfg = json.loads((HERE / "tiny/configs" / f"{name}.json").read_text())
        golden["tiny"][name] = tiny_record(
            weights, reference.forward_logits, cfg)
    peaks = v5e_peaks()
    for name in ("starcoderbase-7b", "cerebras-gpt-6.7b"):
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        golden["work"][name] = work_record(
            weights.dims(cfg), work,
            lambda rows, dm: work.int8_gemv_least_seconds(rows, dm, peaks))
    OUT.write_text(json.dumps(golden, indent=1) + "\n")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main(Path(sys.argv[1]))
