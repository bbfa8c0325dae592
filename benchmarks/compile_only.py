"""Rehearsal 2: compile a configuration's two programs at their real
size for a v5e that is described, not attached (no chip time):

    JAX_PLATFORMS=cpu python benchmarks/compile_only.py <config>

The engine is the one the configuration's family builds
(`families/<family>/build.py` over its `weights.dims`; the first line
printed names the family), handed shapes in place of weights. Prints
the bytes of the serving weights and of the paged cache, and for `tick`
and `prefill` the compile seconds and XLA's memory analysis on one
chip. What the TPU's compiler refuses (a kernel's tiling, fast
memory, a program too large for 16 GB) it refuses here. Nothing runs:
no time, no result.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4",
                  TPU_WORKER_HOSTNAMES="localhost")
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.run import HERE, family_dir, load_family  # noqa: E402
from mpi_cuda_cnn_tpu.ops import pallas_gemv  # noqa: E402
from mpi_cuda_cnn_tpu.serve.paged_cache import PagedKVCache  # noqa: E402


def tree_bytes(tree) -> int:
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree))


def main(config: str) -> None:
    jax.config.update("jax_enable_compilation_cache", False)
    cfg = json.loads((HERE / "configs" / f"{config}.json").read_text())
    family = load_family(family_dir(cfg, HERE))
    build, dm = family.build, family.weights.dims(cfg)
    print(f"{config}: family {family.name}", flush=True)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices[0])

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    params = jax.eval_shape(
        lambda: build.serving_params(dm, 0, cfg))
    engine = build.engine_of(cfg, dm, params)
    print(f"weights {tree_bytes(params) / 1e9:.3f} GB, cache "
          f"{tree_bytes(engine._pages) / 1e9:.3f} GB in {engine.num_pages} "
          f"pages of {engine.page_size}", flush=True)
    p, pages = jax.tree.map(on_chip, params), jax.tree.map(on_chip,
                                                           engine._pages)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    def view(rows):
        return PagedKVCache(pages=pages,
                            block_table=i32(rows, engine._table_width),
                            page_size=engine.page_size,
                            kernel=engine.attn_kernel)

    s = engine.slots
    live = jax.ShapeDtypeStruct((s,), jnp.bool_, sharding=chip)
    programs = [
        ("tick", engine._tick, (view(s), p, i32(s), i32(s), live)),
        ("prefill", engine._prefill,
         (view(1), p, i32(1, engine.prefill_chunk), i32(), i32())),
    ]
    # Tracing runs on the cpu backend; the kernels must still be Mosaic.
    with mock.patch.object(pallas_gemv, "pallas_interpret", lambda: False):
        for name, fn, args in programs:
            t0 = time.time()
            m = fn.lower(*args).compile().memory_analysis()
            total = (m.argument_size_in_bytes + m.output_size_in_bytes
                     + m.temp_size_in_bytes - m.alias_size_in_bytes)
            print(f"{name}: compiled in {time.time() - t0:.0f} s; arguments "
                  f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
                  f"{m.temp_size_in_bytes / 1e9:.3f} GB, on the chip "
                  f"{total / 1e9:.3f} GB", flush=True)


if __name__ == "__main__":
    main(sys.argv[1])
