"""mpi_cuda_cnn_tpu — a TPU-native CNN training framework.

A from-scratch reimplementation of the *capabilities* of the reference
MPI-CUDA-CNN project (a hand-rolled C/MPI/CUDA CNN trainer) as an idiomatic
JAX/XLA/Pallas framework:

- data:     MNIST-style IDX loading (reference: cnn.c:345-402), dataset
            registry, synthetic data generators, batched input pipelines.
- models:   functional layer/model API with the reference's layer types
            (input/conv/full, reference: cnn.c:15-43) plus pooling, and the
            benchmark model presets (reference net, LeNet-5, CIFAR nets).
- ops:      pure-XLA reference ops and Pallas TPU kernels for conv/dense
            forward+backward (reference: cnn.c:113-247, CUDAcnn.cu:167-195).
- parallel: SPMD data parallelism over a `jax.sharding.Mesh` with XLA
            collectives, replacing the reference's per-sample MPI_Allreduce
            (reference: cnnmpi.c:487-499) with one fused gradient psum per
            batched step; extensible to model axes.
- train:    jitted train/eval loops, SGD semantics matching the reference's
            accumulate-then-apply schedule (reference: cnn.c:445-474),
            checkpoint/resume, metrics.

Design stance: everything on the hot path is traced once under `jax.jit`
(static shapes, `lax` control flow), parameters and activations stay
device-resident in HBM, matmuls/convs run on the MXU in f32 (optional bf16),
and multi-device execution is expressed as shardings over a named mesh, not
explicit message passing.
"""

__version__ = "0.1.0"

from . import data, models, obs, ops, parallel, train, utils  # noqa: F401
