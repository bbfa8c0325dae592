"""Full 4D-mesh parity worker: pipe:2 x model:2 x seq:2 x data:2 on 16
virtual CPU devices — ALL FOUR axes populated at once.

The 8-virtual-device suite can run any three of the four axes together
(tests/test_tp_pp_lm.py); this worker is the missing composition's
witness: one train step on the full 16-device mesh must equal the
single-device serial step exactly (loss AND updated params), proving the
data-axis pmean composes with the pipe psum, the Megatron model-axis
collectives, and the ring-attention seq axis in one program.

Run standalone (`python scripts/fourd16_worker.py`) or via
tests/test_4d_full.py / `make test_4d16`. Prints `4D16OK loss=<x>` on
success, exits nonzero otherwise.
"""

import os
import sys

# Must precede the first jax import: 16 virtual CPU devices. FORCE the
# count — when spawned from the test suite the inherited XLA_FLAGS
# already pins 8 (tests/conftest.py) and must be overridden, not kept.
import re

flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=16"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from mpi_cuda_cnn_tpu.models.transformer import TransformerLM  # noqa: E402
from mpi_cuda_cnn_tpu.parallel.mesh import (  # noqa: E402
    DATA_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    make_mesh,
)
from mpi_cuda_cnn_tpu.parallel.pp_lm import (  # noqa: E402
    pp_lm_microbatch,
    sp_pp_shard_batch,
)
from mpi_cuda_cnn_tpu.parallel.sp import SEQ_AXIS  # noqa: E402
from mpi_cuda_cnn_tpu.parallel.tp_pp_lm import (  # noqa: E402
    make_tp_pp_lm_state,
    make_tp_pp_lm_train_step,
    unstack_tp_blocks,
)
from mpi_cuda_cnn_tpu.train.lm import make_lm_state, make_lm_train_step  # noqa: E402
from mpi_cuda_cnn_tpu.utils.backend import enable_compile_cache  # noqa: E402


def main(fast: bool = False) -> None:
    devices = jax.devices()
    assert len(devices) >= 16, f"need 16 virtual devices, got {len(devices)}"

    # --fast: the default-suite CANARY (tests/test_4d_canary.py) — the
    # same 2x2x2x2 composition at the smallest shapes every axis allows
    # (pipe:2 -> 2 blocks, model:2 -> 2 heads, seq:2 -> 2 seq shards,
    # data:2 x 2 microbatches -> batch 4), so the flagship 4D program
    # cannot regress between --runslow runs while the spawn stays in
    # the fast suite's time budget. XLA compile dominates the spawn
    # (~12 s of its ~16 s cold); the persistent compilation cache
    # (utils/backend.enable_compile_cache: JAX_COMPILATION_CACHE_DIR if
    # set, else <checkout>/.cache/jax) brings the steady-state run to
    # < 8 s (measured), and only the first run on a fresh checkout pays
    # the compile.
    if fast:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        model = TransformerLM(vocab=16, dim=16, heads=2, depth=2,
                              max_seq=32)
        toks_shape = (4, 17)
    else:
        model = TransformerLM(vocab=32, dim=32, heads=4, depth=4,
                              max_seq=64)
        toks_shape = (8, 33)
    opt = optax.sgd(0.1)
    rng = np.random.default_rng(2)
    toks = jnp.asarray(
        rng.integers(0, model.vocab, toks_shape), jnp.int32
    )
    tokens, targets = toks[:, :-1], toks[:, 1:]

    seq = toks_shape[1] - 1
    serial_step = make_lm_train_step(model, opt, attn_impl="oracle",
                                     seq_len=seq, donate=False)
    want_state, want_m = serial_step(make_lm_state(model, opt, seed=0),
                                     tokens, targets)

    mesh = make_mesh(
        {PIPE_AXIS: 2, MODEL_AXIS: 2, SEQ_AXIS: 2, DATA_AXIS: 2},
        devices=devices[:16],
    )
    params = model.init(jax.random.key(0))
    state = make_tp_pp_lm_state(model, params, opt, mesh)
    step = make_tp_pp_lm_train_step(model, opt, mesh, state, donate=False,
                                    attn_impl="ring")
    mb = sp_pp_shard_batch(pp_lm_microbatch(tokens, targets, 2), mesh)
    got_state, got_m = step(state, *mb)

    np.testing.assert_allclose(float(got_m["loss"]), float(want_m["loss"]),
                               rtol=1e-5, atol=1e-6)
    got = unstack_tp_blocks(jax.device_get(got_state["params"]), model)
    for a, b in zip(jax.tree.leaves(got),
                    jax.tree.leaves(jax.device_get(want_state["params"]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    print(f"4D16OK loss={float(got_m['loss']):.6f} devices=16 "
          f"mesh=pipe:2,model:2,seq:2,data:2")


if __name__ == "__main__":
    main(fast="--fast" in sys.argv[1:])
