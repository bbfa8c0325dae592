"""Shared timing recipe for the benchmark scripts.

What is true on the TPU v5e the builders have (chip run of PR 21,
2026-09-26, jax 0.9.0 — PERF.md "Bring-up on the v5e"):

- `jax.block_until_ready` WAITS: a 1.89 s matmul chain blocks for
  1.891 s and a host fetch afterwards returns in 2.5 ms. The
  `hard_block` this module used to export (block + a device->host fetch
  of the smallest leaf) was written for an earlier installation where
  the block returned at enqueue; it is gone, and every timing in the
  framework syncs with `jax.block_until_ready`.
- A dependent dispatch costs ~0.19 ms, one dispatch + sync ~0.6 ms, and
  the fixed cost per timed window — what `two_point` exists to cancel —
  measures 0 +- 4 ms. So T(n)/n over a window of tens of milliseconds
  is already within a few percent of the slope; `two_point` stays only
  as the recipe the `scripts/bench_*`/`profile_*` family shares (its
  median also absorbs a stray slow window), and goes when ROADMAP D4
  replaces those scripts.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax


def two_point(run, n: int, *, warmup: int = 1, reps: int = 3) -> float:
    """Per-iteration time: median of `reps` samples of (T(2n) - T(n)) / n.

    The two-point core every benchmark script routes through
    (scan_two_point below, scripts/bench_lm, scripts/check_gqa_flash,
    scripts/profile_lm), so the recipe cannot drift per script.

    `run(k)` must execute k DEPENDENT iterations (so XLA cannot overlap
    or elide them), force completion (`jax.block_until_ready` or a host
    fetch), and return elapsed seconds. The T(2n) - T(n) difference
    cancels any fixed per-window cost (on the v5e: ~0, see the module
    docstring); the MEDIAN over `reps` window pairs absorbs a stray slow
    window; sub-10% differences are not resolvable from one sample.
    The warmup call absorbs compilation for run(k)'s cache entries;
    callers whose per-k programs compile per distinct k should warm both
    sizes themselves and pass warmup=0.
    """
    if warmup:
        run(warmup)
    samples = []
    for _ in range(max(reps, 1)):
        t1 = run(n)
        t2 = run(2 * n)
        samples.append((t2 - t1) / n)
    return sorted(samples)[len(samples) // 2]


def grad_stacked(fn):
    """fwd+bwd measurement target for `scan_two_point`: gradients of
    sum(fn(*args)²) wrt every positional arg, stacked into ONE array so
    the scan body's output-sum DCE defeat covers all gradient leaves.
    One definition for every script that times a backward
    (bench_attention --bwd, check_gqa_flash) — the grad-stack idiom
    must not drift per script any more than the window recipe."""

    def wrapped(*args):
        g = jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
            argnums=tuple(range(len(args))),
        )(*args)
        return jnp.stack([jnp.sum(t.astype(jnp.float32)) for t in g])

    return wrapped


def scan_two_point(fn, n: int, *args, reps: int = 3) -> float:
    """Per-call seconds of `fn(*args)` via `two_point` over ON-DEVICE
    scan windows — the micro-op form of the shared recipe (scripts/
    bench_attention.py, bench_conv_shapes.py):

    - a window of m calls is one jitted `lax.scan` of m iterations; the
      body perturbs the first operand per step (defeats CSE; the factor
      is computed in f32 then CAST BACK so bf16 operands stay bf16 —
      naive `x * (1 + i*1e-9)` promotes to f32 and benches the wrong
      kernel) and accumulates a f32 sum of the output (defeats DCE);
    - `float()` on the scan result is the sync (a host fetch cannot
      complete before the value exists);
    - window cancellation + median over `reps` come from `two_point`.
    """

    def make(m):
        @jax.jit
        def run(args):
            def body(acc, i):
                a0 = args[0] * (1.0 + i * 1e-9).astype(args[0].dtype)
                out = fn(a0, *args[1:])
                return acc + jnp.sum(out.astype(jnp.float32)), None

            acc, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                              jnp.arange(m, dtype=jnp.float32))
            return acc

        return run

    progs = {m: make(m) for m in (n, 2 * n)}
    for p in progs.values():  # compile + warm both sizes
        float(p(args))

    def run(m):
        t0 = time.perf_counter()
        float(progs[m](args))
        return time.perf_counter() - t0

    return two_point(run, n, warmup=0, reps=reps)
