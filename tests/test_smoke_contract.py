"""`chip_smoke.py`'s contract off the chip: with no arguments and no
TPU it exits non-zero in seconds, compiles nothing and prints no result
line; `--rehearse` is the one sanctioned CPU run — the same phases
through the real CLI entry points at toy shapes, every line stamped as
a rehearsal, so it can never be read as a chip run.

(This file sorts after test_paged_kernel.py on purpose: the rehearsal
is the slowest test PR 21 added, and it sits behind the ~95 s that PR
saved there, so the suite's running time stays ahead of its parent's
at every point of the tier-1 order.)"""

import json
import subprocess
import sys

import pytest

from test_bench_contract import REPO, assert_refused_without_chip, run_script

# Compile-only: libtpu builds a v5e 2x2 topology with no chip attached,
# so the real XLA:TPU + Mosaic compile of a four-chip program runs in
# the sandbox (.claude/skills/verify/SKILL.md). Own process: libtpu is
# loaded once per process and must see these variables first.
_AOT_FOUR_CHIP = """
import os
os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4",
                  TPU_WORKER_HOSTNAMES="localhost", JAX_PLATFORMS="cpu")
import sys
from unittest import mock
import jax, jax.numpy as jnp, numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
try:
    devices = topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices
except Exception as e:
    print("NO_TOPOLOGY", e); sys.exit(0)
from mpi_cuda_cnn_tpu.ops import pallas_attention
from mpi_cuda_cnn_tpu.train.lm import get_attn_fn
mesh = Mesh(np.array(devices), ("data",))
x = jax.ShapeDtypeStruct((8, 256, 2, 128), jnp.bfloat16,
                         sharding=NamedSharding(mesh, P("data")))
with mock.patch.object(pallas_attention, "pallas_interpret", lambda: False):
    text = jax.jit(get_attn_fn("flash", mesh)).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text
    try:
        jax.jit(get_attn_fn("flash")).lower(x, x, x).compile()
    except NotImplementedError as e:
        assert "automatically partitioned" in str(e), e
    else:
        raise AssertionError("bare Mosaic kernel partitioned by GSPMD?")
print("FOUR_CHIP_OK")
"""

# The same for one chip and ops/pallas_expert_mlp's kernel at the
# widths smallthinker-21ba3b-instruct publishes (PR 33): 3,072 sorted
# rows of a 512-row chunk and a tick's 256, 64 experts of 3 x (2560,
# 768) bf16 -- whole matrices as blocks, 2 x 11.8 MB of them in VMEM.
_AOT_EXPERT_MLP = """
import os
os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4",
                  TPU_WORKER_HOSTNAMES="localhost", JAX_PLATFORMS="cpu")
import sys
from unittest import mock
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices[0])
except Exception as e:
    print("NO_TOPOLOGY", e); sys.exit(0)
from mpi_cuda_cnn_tpu.ops import pallas_expert_mlp as pem
sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                             sharding=chip)
bank = {"wg": sds((64, 2560, 768), "bfloat16"),
        "wu": sds((64, 2560, 768), "bfloat16"),
        "wd": sds((64, 768, 2560), "bfloat16")}
assert pem.fits(2560, 768, 2) and not pem.fits(7168, 2048, 2)
with mock.patch.object(pem, "pallas_interpret", lambda: False):
    # ... the tick's under an ambient "highest", as chip_smoke.py's twin
    # and a reference set it: Mosaic takes bf16 at one precision only.
    for rows, ambient in ((3072, "default"), (256, "highest")):
        with jax.default_matmul_precision(ambient):
            text = jax.jit(lambda x, s, b: pem.expert_mlp(
                x, s, b, jax.nn.relu)).lower(
                sds((rows, 2560), "bfloat16"), sds((64,), "int32"),
                bank).compile().as_text()
        assert "tpu_custom_call" in text and "expert_mlp" in text
print("EXPERT_MLP_OK")
"""


# The same for minicpm-sala's two mixers at the cell's layout (PR 34):
# one sparse layer's write, selection and read over 32 slots x 65,536
# rows of 2 K/V heads, and one linear layer's product over 32 heads of
# 128 -- a tick (argv 1 = "tick") or a 512-row chunk ("chunk"). No
# kernel of this repo's is in them: what the compile shows is that
# XLA's own forms fit beside 10.5 GB of weights, cache and states.
_AOT_SPARSE_LINEAR = """
import os
os.environ.update(TPU_ACCELERATOR_TYPE="v5litepod-4",
                  TPU_WORKER_HOSTNAMES="localhost", JAX_PLATFORMS="cpu")
import sys
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        topology_name="v5e:2x2", platform="tpu").devices[0])
except Exception as e:
    print("NO_TOPOLOGY", e); sys.exit(0)
from mpi_cuda_cnn_tpu.models.generate import linear_attend
from mpi_cuda_cnn_tpu.models.transformer import LinearAttn, SparseSelect
from mpi_cuda_cnn_tpu.serve import paged_cache
sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                             sharding=chip)
b, kk = (32, 1) if sys.argv[1] == "tick" else (1, 512)
pages, sel = 32 * 4096 + 1, SparseSelect()
pools = {"k": sds((pages, 16, 2, 128), "bfloat16"),
         "v": sds((pages, 16, 2, 128), "bfloat16"),
         "kc": sds((pages, 1, 2, 128), "bfloat16")}
sparse = jax.jit(lambda c, q, k, v, pos, ok, table:
                 paged_cache.paged_update_attend(
                     c, q, k, v, pos, ok, table, 16, select=sel)).lower(
    pools, sds((b, kk, 32, 128), "bfloat16"), sds((b, kk, 2, 128), "bfloat16"),
    sds((b, kk, 2, 128), "bfloat16"), sds((b, kk), "int32"),
    sds((b, kk), "bool"), sds((b, 4096), "int32")).compile()
text = sparse.as_text()
assert ("while" in text) and "attn.sparse_select" in text
# PR 35: a tick gathers no slot's whole table of compressed keys (32 x
# 4,096 rows), a chunk finds its 64th-best block score without a sort.
assert "[131072,2,128]" not in text if kk == 1 else " sort(" not in text
row = sds((b, kk, 32, 128), "bfloat16")
linear = jax.jit(lambda q, k, v, s, ok: linear_attend(
    q, k, v, s, ok, LinearAttn().log_decay(32))).lower(
    row, row, row, sds((b, 32, 128, 128), "float32"),
    sds((b, kk), "bool")).compile()
for name, c in (("sparse", sparse), ("linear", linear)):
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < 1 << 30, (name, temp)
print("SPARSE_LINEAR_OK")
"""


def test_chip_smoke_without_chip_exits_nonzero_and_prints_no_result():
    assert_refused_without_chip(run_script("chip_smoke.py", timeout=120))


def test_chip_smoke_rehearsal_passes_on_cpu():
    """Every phase at toy shapes through the real CLI entry points; the
    last line has the driver's shape but can never be read as a chip
    run."""
    proc = run_script("chip_smoke.py", "--rehearse", timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    assert all(l["rehearsal"] is True and l["platform"] == "cpu"
               and l["device_kind"] == "cpu" and l["device_count"] == 1
               for l in lines)
    phases = {l["phase"]: l for l in lines if l.get("event") == "phase"}
    assert set(phases) == {"kernels", "cnn", "lm", "serve_default",
                           "serve_serving_config"}
    assert all(p["ok"] for p in phases.values())
    assert phases["serve_serving_config"]["weights_dtype"] == "int8"
    assert lines[-1]["ok"] is True
    assert lines[-1]["device"] == {"platform": "cpu", "kind": "cpu",
                                   "count": 1}


def test_flash_kernel_compiles_for_a_four_chip_data_mesh():
    """The first four-chip run (PR 21) failed in the LM step: "Mosaic
    kernels cannot be automatically partitioned". Virtual CPU devices
    cannot show that (the interpreted kernel partitions fine), but a
    compile-only v5e topology can: the bare kernel must still be
    refused under GSPMD, and get_attn_fn's mesh-aware form must compile
    to a Mosaic custom call."""
    pytest.importorskip("libtpu")
    proc = subprocess.run([sys.executable, "-c", _AOT_FOUR_CHIP],
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO)
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip(f"no compile-only TPU topology here: {proc.stdout}")
    assert proc.returncode == 0 and "FOUR_CHIP_OK" in proc.stdout, \
        proc.stderr[-3000:]


def test_expert_mlp_kernel_compiles_at_the_published_widths():
    """Interpret mode knows no VMEM: whole (2560, 768) matrices as
    blocks, twice over, need the kernel's raised scope, and only the
    chip's compiler can say that they fit (PR 33)."""
    pytest.importorskip("libtpu")
    proc = subprocess.run([sys.executable, "-c", _AOT_EXPERT_MLP],
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO)
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip(f"no compile-only TPU topology here: {proc.stdout}")
    assert proc.returncode == 0 and "EXPERT_MLP_OK" in proc.stdout, \
        proc.stderr[-3000:]


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_sparse_and_linear_mixers_compile_at_the_published_widths(program):
    """The selection's scores of a 512-row chunk against 4,096
    compressed keys, its top-64 of 1,024 blocks for 1,024 (row, head)
    pairs, the masked read of every block to 65,536 rows and the
    (32, 512, 512) decay products are shapes no CPU test reaches; only
    the chip's compiler can say that each layer's temporaries stay
    under 1 GB beside the cell's 10.5 GB (PR 34)."""
    pytest.importorskip("libtpu")
    proc = subprocess.run([sys.executable, "-c", _AOT_SPARSE_LINEAR, program],
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO)
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip(f"no compile-only TPU topology here: {proc.stdout}")
    assert proc.returncode == 0 and "SPARSE_LINEAR_OK" in proc.stdout, \
        proc.stderr[-3000:]
