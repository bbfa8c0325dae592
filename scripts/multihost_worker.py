"""One process of a multi-host DP training demo.

The multi-host twin of the reference's `mpirun -np 8` world (Makefile:44):
each process calls `jax.distributed.initialize` (the MPI_Init replacement,
cnnmpi.c:419), after which `jax.devices()` is the GLOBAL device list and
the ordinary DP train step runs unchanged — collectives cross process
boundaries via the runtime (ICI/DCN on a real pod; TCP here on CPU).

Usage (one line per "host"):
    python scripts/multihost_worker.py <pid> <nproc> <coordinator> \
        [devs_per_proc] [mode]

mode "cnn" (default): the DP CNN step. mode "lm": RING sequence
parallelism for the transformer LM over the GLOBAL mesh — the k/v blocks
ppermute across the OS-process boundary (multi-host long context).
mode "pp": GPipe pipeline parallelism with the stage boundary ON the
process boundary — a ('pipe': 2, 'data': gdev/2) mesh places stage 0's
devices in process 0 and stage 1's in process 1, so every microbatch
activation (and its cotangent in backward) ppermutes between processes.

Every process feeds the SAME global batch (the reference's every-rank-
loads-the-full-dataset pattern, cnnmpi.c:426-454, made correct); the
printed loss must therefore be identical on every process.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve().parent.parent))


def _synthetic_batch(batch):
    """Same seed in every process -> the SAME global batch everywhere (the
    reference's every-rank-loads-the-full-dataset pattern, made correct)."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.random((batch, 28, 28, 1), np.float32))
    y = np.zeros((batch, 10), np.float32)
    y[np.arange(batch), rng.integers(0, 10, batch)] = 1.0
    return x, jnp.asarray(y)


def _print_mhok(info, metrics) -> int:
    """The one line tests/test_multihost.py greps; metrics are replicated
    (P() out-specs), so float() is safe in every process."""
    import jax

    jax.block_until_ready(metrics)
    print(
        f"MHOK pid={info.process_index} procs={info.process_count} "
        f"gdev={info.global_devices} loss={float(metrics['loss']):.6f}",
        flush=True,
    )
    return 0


def main() -> int:
    pid, nproc = int(sys.argv[1]), int(sys.argv[2])
    coordinator = sys.argv[3]
    devs = int(sys.argv[4]) if len(sys.argv) > 4 else 4
    mode = sys.argv[5] if len(sys.argv) > 5 else "cnn"

    import jax

    # In-process CPU selection: the worker is a CPU demo whatever
    # JAX_PLATFORMS its parent exported.
    jax.config.update("jax_platforms", "cpu")
    # Replace (don't append to) any inherited device-count flag — e.g. the
    # one tests/conftest.py exports — so XLA never sees two conflicting
    # occurrences.
    import re

    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        os.environ.get("XLA_FLAGS", ""),
    )
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={devs}"
    ).strip()
    from mpi_cuda_cnn_tpu.parallel.distributed import initialize_distributed

    info = initialize_distributed(
        coordinator_address=coordinator, num_processes=nproc, process_id=pid
    )
    assert info.process_count == nproc, info

    import jax.numpy as jnp

    if mode == "lm":
        return _lm_main(info)
    if mode == "pp":
        return _pp_main(info)
    if mode == "4d":
        return _4d_main(info)

    from mpi_cuda_cnn_tpu.models.initializers import get_initializer
    from mpi_cuda_cnn_tpu.models.presets import get_model
    from mpi_cuda_cnn_tpu.parallel.dp import (
        dp_shard_batch,
        make_dp_train_step,
        replicate,
    )
    from mpi_cuda_cnn_tpu.parallel.mesh import make_mesh
    from mpi_cuda_cnn_tpu.train.optimizer import make_optimizer
    from mpi_cuda_cnn_tpu.train.trainer import make_loss_fn

    mesh = make_mesh()  # all GLOBAL devices on the data axis
    model = get_model("reference_cnn")
    params = model.init(jax.random.key(0), get_initializer("normal"))
    optimizer = make_optimizer(0.1)
    state = replicate(
        {"params": params, "opt_state": optimizer.init(params),
         "step": jnp.zeros((), jnp.int32)},
        mesh,
    )
    step = make_dp_train_step(make_loss_fn(model), optimizer, mesh, donate=False)

    x, y = _synthetic_batch(2 * info.global_devices)
    xs, ys = dp_shard_batch((x, y), mesh)

    state, metrics = step(state, xs, ys)
    return _print_mhok(info, metrics)


def _lm_main(info) -> int:
    """Ring-SP LM step over the global mesh: every device holds S/gdev
    tokens; k/v blocks rotate through EVERY device — including across
    the process boundary (the multi-host long-context path)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
    from mpi_cuda_cnn_tpu.parallel.mesh import make_mesh
    from mpi_cuda_cnn_tpu.parallel.sp import SEQ_AXIS, make_sp_lm_train_step

    gdev = info.global_devices
    mesh = make_mesh({SEQ_AXIS: gdev})
    # GQA + rope: the round-2 features ride the multi-host ring too.
    model = TransformerLM(vocab=13, dim=16, heads=4, depth=1,
                          max_seq=8 * gdev, kv_heads=2, pos="rope")
    params = model.init(jax.random.key(0))
    opt = optax.sgd(0.1)
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    step = make_sp_lm_train_step(model, opt, mesh, impl="ring",
                                 donate=False)
    rng = np.random.default_rng(7)  # same seed everywhere -> same tokens
    toks = jnp.asarray(rng.integers(0, 13, (2, 8 * gdev + 1)), jnp.int32)
    _, metrics = step(state, toks[:, :-1], toks[:, 1:])
    return _print_mhok(info, metrics)


def _pp_main(info) -> int:
    """2-stage GPipe across the process boundary: with 2 processes and
    the 'pipe' axis outermost, stage 0 lives entirely in process 0 and
    stage 1 in process 1 — the forward activation handoff and the
    backward cotangent handoff both cross OS processes (the multi-host
    pipeline path; the reference never pipelined at all)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_cuda_cnn_tpu.models.initializers import get_initializer
    from mpi_cuda_cnn_tpu.models.presets import get_model
    from mpi_cuda_cnn_tpu.parallel.mesh import DATA_AXIS, PIPE_AXIS, make_mesh
    from mpi_cuda_cnn_tpu.parallel.pp import (
        make_pipeline_plan,
        make_pp_state,
        make_pp_train_step,
        microbatch,
        pp_shard_batch,
    )
    from mpi_cuda_cnn_tpu.train.optimizer import make_optimizer

    gdev = info.global_devices
    mesh = make_mesh({PIPE_AXIS: 2, DATA_AXIS: gdev // 2})
    model = get_model("reference_cnn")
    params = model.init(jax.random.key(0), get_initializer("normal"))
    optimizer = make_optimizer(0.1)
    plan = make_pipeline_plan(model, 2)
    state = make_pp_state(plan, params, optimizer, mesh)
    step = make_pp_train_step(plan, optimizer, mesh, state, donate=False)

    x, y = _synthetic_batch(2 * gdev)  # divisible by M x data = 2 x gdev/2
    x_mb, y_mb = pp_shard_batch(microbatch(x, y, 2), mesh)

    state, metrics = step(state, x_mb, y_mb)
    return _print_mhok(info, metrics)


def _4d_main(info) -> int:
    """The LM's full pipe x model x seq mesh split over 2 OS processes:
    'pipe' outermost puts the GPipe stage boundary ON the process
    boundary, while the Megatron psums (over 'model') and the ring
    attention ppermutes (over 'seq') run within each process — the
    layout a real pod uses (TP/SP inside a host on ICI, PP across on
    DCN). Every collective family the framework has crosses or rides
    the distributed runtime in ONE step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
    from mpi_cuda_cnn_tpu.parallel.mesh import MODEL_AXIS, PIPE_AXIS, make_mesh
    from mpi_cuda_cnn_tpu.parallel.pp_lm import (
        pp_lm_microbatch,
        sp_pp_shard_batch,
    )
    from mpi_cuda_cnn_tpu.parallel.sp import SEQ_AXIS
    from mpi_cuda_cnn_tpu.parallel.tp_pp_lm import (
        make_tp_pp_lm_state,
        make_tp_pp_lm_train_step,
    )

    assert info.global_devices == 8, info
    mesh = make_mesh({PIPE_AXIS: 2, MODEL_AXIS: 2, SEQ_AXIS: 2})
    model = TransformerLM(vocab=13, dim=16, heads=2, depth=2, max_seq=16)
    params = model.init(jax.random.key(0))
    opt = optax.sgd(0.1)
    state = make_tp_pp_lm_state(model, params, opt, mesh)
    step = make_tp_pp_lm_train_step(model, opt, mesh, state,
                                    donate=False, attn_impl="ring")
    rng = np.random.default_rng(7)  # same seed everywhere -> same tokens
    toks = jnp.asarray(rng.integers(0, 13, (2, 17)), jnp.int32)
    mb = sp_pp_shard_batch(
        pp_lm_microbatch(toks[:, :-1], toks[:, 1:], 2), mesh
    )
    state, metrics = step(state, *mb)
    return _print_mhok(info, metrics)


if __name__ == "__main__":
    sys.exit(main())
