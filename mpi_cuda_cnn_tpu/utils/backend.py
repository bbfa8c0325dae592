"""Which machine is this — the one place every entry point asks.

Four questions used to be answered separately at each call site, each
with its own silent default: is this a chip (`--device`), should a
Pallas kernel be interpreted, what ran (the stamp a result carries), and
where compiled programs are kept. A default that quietly picks the CPU
or the interpreter makes a sandbox run look like a chip run, so here
each question has one answer and no fallback: the platform is `cpu`
(tests, laptops — Pallas interpreted) or `tpu` (Mosaic-compiled), and
anything else is an error rather than a guess.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

import jax

# <checkout>/.cache/jax — a FIXED path: the directory is part of the
# persistent cache's key, so one that moved between runs would never hit.
_DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".cache" / "jax"


class DeviceError(RuntimeError):
    """The requested device is not the one JAX found."""


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache; return its directory.

    `JAX_COMPILATION_CACHE_DIR` set: JAX already reads it — touch
    nothing (whoever launched the process decides where the cache
    lives). Unset: <checkout>/.cache/jax. Every jax-using entry point
    calls this before its first compile; it is the only site in the
    repo that sets `jax_compilation_cache_dir`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE_DIR))
    return str(_DEFAULT_CACHE_DIR)


def select_device(device: str) -> None:
    """Honor `--device auto|tpu|cpu` (the north star's switch,
    BASELINE.json). `cpu` pins the platform in-process; `tpu` means the
    default backend IS a TPU, else DeviceError — never "some accelerator",
    never a fallback; `auto` takes what JAX found (laptops), and the
    caller's first log line and first record say what that was
    (`device_stamp`)."""
    if device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif device == "tpu":
        if jax.default_backend() != "tpu":
            raise DeviceError(
                "--device=tpu requested but the backend is "
                f"{jax.default_backend()!r}"
            )
    elif device != "auto":
        raise DeviceError(f"unknown --device {device!r} (want auto|tpu|cpu)")


def device_stamp(mesh=None) -> dict:
    """What ran, as JAX reports it — carried by every entry point's
    first log line and first JSONL record, and by every benchmark
    result, so a CPU number can never be read as a chip number."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": dict(mesh.shape) if mesh is not None else None,
    }


def claim_device(device: str, say=None) -> dict:
    """What every jax-using entry point does first: honor --device
    (DeviceError if it is not there), place the compile cache, and say
    what was found — the entry point's first log line, through `say`
    (default: stderr). Returns the stamp for the first JSONL record."""
    select_device(device)
    enable_compile_cache()
    stamp = device_stamp()
    line = ("device: platform={platform} device_kind={device_kind} "
            "device_count={device_count}").format(**stamp)
    if say is None:
        print(line, file=sys.stderr)
    else:
        say(line)
    return stamp


def pallas_interpret() -> bool:
    """The `interpret=` argument of every pallas_call in the repo: True
    on platform `cpu` (the tier-1 suite runs the real kernel bodies
    through the Pallas interpreter), False on `tpu` (Mosaic), an error
    anywhere else — an unknown platform must not silently interpret."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise DeviceError(
        f"Pallas kernels here run on 'tpu' (compiled) or 'cpu' "
        f"(interpreted); the backend is {platform!r}"
    )
