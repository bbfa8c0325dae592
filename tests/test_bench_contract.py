"""`bench.py`'s no-chip contract, and the helpers behind it
(utils/backend): the benchmark needs a TPU — without one it exits
non-zero in seconds, compiles nothing and prints no metric line, so a
CPU epoch time can never be recorded under the chip metric's name.
(`chip_smoke.py`'s side of the same contract, and its CPU rehearsal,
are in tests/test_smoke_contract.py.)"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from mpi_cuda_cnn_tpu import obs
from mpi_cuda_cnn_tpu.utils import backend

REPO = Path(__file__).resolve().parent.parent


def run_script(script, *args, timeout):
    """Run a repo-root script the way the sandbox does: JAX_PLATFORMS=cpu
    and ONE CPU device (the conftest's 8 virtual devices would make a
    rehearsal an 8-way data-parallel run)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(REPO / script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def assert_refused_without_chip(proc):
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == "", proc.stdout
    assert "tpu" in proc.stderr.lower()


def test_bench_without_chip_exits_nonzero_and_prints_no_metric():
    assert_refused_without_chip(run_script("bench.py", timeout=120))


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: touch nothing. Unset: the fixed
    <checkout>/.cache/jax — never a temp dir, a pid or the clock (the
    path is part of the cache key)."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert backend.enable_compile_cache() == "/somewhere/else"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = str(REPO / ".cache" / "jax")
    assert backend.enable_compile_cache() == want
    assert backend.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)] * 2


def test_interpret_and_device_helpers_refuse_unknown_platforms(monkeypatch):
    assert backend.pallas_interpret() is True          # the suite: cpu
    backend.select_device("auto")
    backend.select_device("cpu")
    with pytest.raises(backend.DeviceError, match="tpu"):
        backend.select_device("tpu")                   # no chip here
    with pytest.raises(backend.DeviceError, match="unknown"):
        backend.select_device("gpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.pallas_interpret() is False
    backend.select_device("tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(backend.DeviceError, match="'gpu'"):
        backend.pallas_interpret()


def test_peak_flops_is_keyed_by_device_kind():
    assert obs.peak_flops("bfloat16") is None          # cpu: no peak
    assert obs.peak_flops("bfloat16", device_kind="cpu") is None
    bf16 = obs.peak_flops("bfloat16", device_kind="TPU v5 lite")
    assert bf16 == obs.PEAK_TFLOPS["TPU v5 lite"]["bfloat16"] * 1e12
    assert obs.peak_flops("float32", device_kind="TPU v5 lite") == bf16 / 4
    with pytest.raises(ValueError, match="TPU v9"):
        obs.peak_flops("bfloat16", device_kind="TPU v9")
    assert obs.peak_flops("bfloat16", device_kind="TPU v9",
                          override_tflops=100.0) == 100e12
    assert 0 < obs.mfu(bf16 / 2, 1.0, bf16) == 0.5
    assert obs.mfu(1e12, 1.0, None) is None
