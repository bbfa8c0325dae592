"""Run the five BASELINE.json benchmark configurations and print one JSON
line per config: {"config", "model", "dataset", "mesh", "epochs",
"epoch_seconds", "test_accuracy"}.

The five configs (BASELINE.json "configs"):
  1. LeNet-5 on MNIST, single-process          (cnn.c reference twin)
  2. LeNet-5 on MNIST, 4-way data-parallel     (cnnmpi.c twin)
  3. LeNet-5 on Fashion-MNIST, 8-way DP
  4. 3-conv CNN on CIFAR-10 (32x32x3 path)
  5. VGG-small on CIFAR-10, 8-way DP

Real IDX data is used when --data-dir has it; otherwise shape-identical
synthetic sets (this environment has no network — SURVEY.md §4). Multi-way
DP configs need >= that many devices: on a single TPU chip they fall back
to a 1-device mesh and say so in the JSON ("mesh" reports what actually
ran).

Usage: python scripts/bench_configs.py [--epochs N] [--data-dir DIR]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


CONFIGS = [
    # (name, model, dataset, requested data-axis size)
    ("lenet5_mnist_serial", "lenet5", "mnist", 1),
    ("lenet5_mnist_dp4", "lenet5", "mnist", 4),
    ("lenet5_fashion_dp8", "lenet5", "fashion_mnist", 8),
    ("cifar3conv_cifar10", "cifar3conv", "cifar10", 1),
    ("vgg_small_cifar10_dp8", "vgg_small", "cifar10", 8),
]

SYNTHETIC_FALLBACK = {
    "mnist": "synthetic",
    "fashion_mnist": "synthetic",
    "cifar10": "synthetic_cifar",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--num-train", type=int, default=8192,
                    help="synthetic-set size when real data is absent")
    ap.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    ap.add_argument("--configs", default=None,
                    help="comma-separated substring filter on config names "
                         "(e.g. 'lenet5,cifar3conv')")
    args = ap.parse_args()

    import jax

    from mpi_cuda_cnn_tpu.utils.backend import claim_device

    claim_device(args.device)  # utils/backend: DeviceError off-chip

    from mpi_cuda_cnn_tpu.data.datasets import get_dataset
    from mpi_cuda_cnn_tpu.models.presets import get_model
    from mpi_cuda_cnn_tpu.train.trainer import Trainer
    from mpi_cuda_cnn_tpu.utils.config import Config
    from mpi_cuda_cnn_tpu.utils.logging import MetricsLogger

    ndev = len(jax.devices())
    wanted = args.configs.split(",") if args.configs else None
    for name, model, dataset, want_dp in CONFIGS:
        if wanted is not None and not any(w in name for w in wanted):
            continue
        data_dir = args.data_dir and Path(args.data_dir) / dataset
        if data_dir and (data_dir / "train-images-idx3-ubyte").exists():
            ds = get_dataset(dataset, data_dir=data_dir)
            ds_name = dataset
        else:
            ds_name = SYNTHETIC_FALLBACK[dataset]
            ds = get_dataset(ds_name, num_train=args.num_train, num_test=512)
        n_data = min(want_dp, ndev)
        cfg = Config(
            model=model, dataset=ds_name, epochs=args.epochs, init="he",
            batch_size=32 * n_data, num_devices=n_data, eval_every=0,
            log_every=10**9,
        )
        trainer = Trainer(
            get_model(model), ds, cfg, metrics=MetricsLogger(echo=False)
        )
        result = trainer.train()
        print(json.dumps({
            "config": name,
            "model": model,
            "dataset": ds_name,
            "mesh": {"data": n_data},
            "epochs": args.epochs,
            # Wall-clock of the last trained epoch, ending in
            # block_until_ready. On the v5e that is the device time plus
            # ~5 ms of dispatch (0.0616 vs 0.0566 s for the reference
            # CNN, chip run of PR 21), so the two-point re-measurement
            # this script used to make is gone.
            "epoch_seconds": round(result.epoch_seconds[-1], 4),
            "timing": "wallclock",
            "test_accuracy": round(result.test_accuracy, 4),
        }), flush=True)


if __name__ == "__main__":
    main()
