"""Measure the f32 oracle/flash attention crossover on the real chip.

`train/lm.py pick_attn_impl` routes f32 short-sequence training to the
oracle because the f32 flash kernel's HIGHEST-precision MXU dots run at
1/4 rate; the bound `_F32_FLASH_MIN_SEQ` was interpolated between
measured endpoints at s=2048 (oracle wins) and s=8192 (flash wins).
This script measures the actual crossover: the full f32 train step with
each impl at s in {2048, 3072, 4096, 6144}, two-point timing
(scripts/bench_lm.bench_config), one JSON row per (s, impl)
plus a final row recommending the smallest measured s where flash wins
— the value `_F32_FLASH_MIN_SEQ` should pin, citing data instead of an
interpolation (VERDICT r3 item 6).

Batch is small (default 2): the f32 oracle at s=6144 materializes
(B, H, S, S) scores — 9.6 GB at b=8, within HBM at b=2 — and the
routing constant is a per-shape decision, not a throughput headline.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from bench_lm import bench_config  # noqa: E402  (scripts/ sibling)
from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
from mpi_cuda_cnn_tpu.utils.backend import claim_device


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, nargs="+",
                    default=[2048, 3072, 4096, 6144])
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    args = ap.parse_args()

    claim_device(args.device)  # utils/backend: DeviceError off-chip

    crossover = None
    for s in args.seqs:
        model = TransformerLM(
            vocab=args.vocab, dim=args.dim, heads=args.heads,
            depth=args.depth, max_seq=s,
        )
        row = {"bench": "f32_crossover", "seq": s, "batch": args.batch}
        for impl in ("oracle", "flash"):
            dt, _ = bench_config(
                model, batch=args.batch, seq=s, compute_dtype=None,
                attn_impl=impl, steps=args.steps,
            )
            row[f"{impl}_ms"] = round(dt * 1e3, 2)
        row["flash_wins"] = row["flash_ms"] < row["oracle_ms"]
        if crossover is None and row["flash_wins"]:
            crossover = s
        print(json.dumps(row), flush=True)

    note = (
        "smallest measured s where the f32 flash train step beats the "
        "oracle; pin train/lm._F32_FLASH_MIN_SEQ to this"
        if crossover is not None else
        f"no crossover: the oracle won at every measured s (max "
        f"{max(args.seqs)}); keep _F32_FLASH_MIN_SEQ above that bound"
    )
    print(json.dumps({
        "metric": "f32_flash_min_seq",
        "value": crossover,
        "unit": "positions",
        "note": note,
        "backend": jax.default_backend(),
    }))


if __name__ == "__main__":
    main()
