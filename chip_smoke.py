"""chip_smoke.py — does the system still start on the chip?

    python3 chip_smoke.py              # on a TPU: full width, ~minutes
    python3 chip_smoke.py --rehearse   # anywhere: toy shapes on the CPU

One process drives the three main paths through the entry point a user
calls (`mpi_cuda_cnn_tpu.cli.main`, `--device tpu`) and checks what
comes out by the repo's own records (`--metrics-jsonl`):

- kernels: every Pallas kernel the other phases reach (flash forward
  and both backward kernels, int8 GEMV), called once at the smoke's
  shapes and compared on the device with its XLA twin under
  `jax.default_matmul_precision("highest")`; on the chip the lowered
  program must hold a Mosaic custom call (nothing interpreted). And
  the bounded paged read against the whole-table gather, at the
  benchmark's two K/V head layouts and pool types and at its latent
  layout (64 x 2,048 bf16 rows of 640 lanes, 128 heads).
- cnn: the source paper's path — 4 IDX files, `reference_cnn`, 60,000
  samples, batch 32 per chip, 2 scanned epochs + eval.
- lm: `lm --dim 4096 --depth 3 --heads 32 --seq-len 2048`, bf16, 6 steps.
- serve: `serve-bench --mode continuous` at the same width, twice: the
  defaults (f32 cache) and the serving configuration (GQA-8, auto
  cache/weights dtypes).

With no arguments it needs a TPU and fails before compiling anything
without one; `--rehearse` is the only way it runs on a CPU, at toy
shapes, and every line it then prints says `"rehearsal": true`. Every
stdout line is one JSON object naming platform, device_kind and
device_count; the last is `{"ok": ..., "device": {...}}`. Exit code 0
only if every phase passed. Compile seconds per phase (JAX's own
compile events) are reported as set-up time, with persistent-cache
hits and writes, so a second run shows what the cache saved.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"      # JSONL records (small)
IDX = ROOT / ".cache" / "chip_smoke" / "idx"   # generated dataset (47 MB)

# One LM width for every phase: the d=4096 x 3, 32 heads of 128 shape
# the repo has chip history for (PERF.md). Depth is the only cut.
FULL = dict(
    cnn=dict(train=60_000, test=10_000, per_chip_batch=32),
    lm=dict(dim=4096, depth=3, heads=32, seq=2048, per_chip_batch=2,
            steps=6),
    serve=dict(dim=4096, depth=3, heads=32, kv_heads=8, max_seq=2048,
               slots=8, page_size=16, prompt_max=512, out_max=64,
               requests=8, prefill_chunk=32,
               # The benchmark's two cache layouts (PERF.md section 4).
               paged=dict(
                   chat=dict(kv_heads=32, cache_dtype="bfloat16", slots=8,
                             max_len=2048),
                   generation=dict(kv_heads=1, cache_dtype="int8", slots=16,
                                   max_len=1024)),
               # ... and its latent layout (`dots.vlm1.inst`): one row a
               # token that all heads read, no V pool.
               latent=dict(heads=128, kv_rank=512, nope=128, rope=64, v=128,
                           lanes=640, cache_dtype="bfloat16", slots=64,
                           max_len=2048),
               # ... and the expert layer of `smallthinker-21ba3b-
               # instruct`: a 512-row chunk's 3,072 pairs over 64 held
               # experts of (2560, 768).
               experts=dict(rows=512, experts=64, top_k=6, dim=2560,
                            width=768),
               # ... and `minicpm-sala`'s two mixers: a sparse layer's
               # pools (2 K/V heads, a compressed key every 16 rows) with
               # the selection's published sizes, and a linear layer's
               # 32 states a slot.
               sparse=dict(heads=32, kv_heads=2, head_dim=128,
                           cache_dtype="bfloat16", slots=32, max_len=65536,
                           chunk=512, select=dict(
                               kernel=32, stride=16, block=64, topk=64,
                               init_blocks=1, window=2048, dense_len=8192))),
)
# Same phases, same code paths, sizes a CPU finishes in seconds.
TOY = dict(
    cnn=dict(train=2_000, test=500, per_chip_batch=32),
    lm=dict(dim=32, depth=1, heads=2, seq=128, per_chip_batch=2, steps=6),
    serve=dict(dim=32, depth=1, heads=4, kv_heads=2, max_seq=64,
               slots=2, page_size=8, prompt_max=16, out_max=8,
               requests=3, prefill_chunk=8,
               paged=dict(
                   chat=dict(kv_heads=4, cache_dtype="bfloat16", slots=4,
                             max_len=64),
                   generation=dict(kv_heads=1, cache_dtype="int8", slots=5,
                                   max_len=48)),
               latent=dict(heads=4, kv_rank=32, nope=8, rope=8, v=8,
                           lanes=128, cache_dtype="bfloat16", slots=4,
                           max_len=64),
               experts=dict(rows=96, experts=8, top_k=2, dim=32, width=16),
               sparse=dict(heads=4, kv_heads=2, head_dim=16,
                           cache_dtype="bfloat16", slots=3, max_len=256,
                           chunk=16, select=dict(
                               kernel=4, stride=2, block=8, topk=2,
                               init_blocks=1, window=16, dense_len=32))),
)
# A tick slower than this is a compile (or a stall) inside the serving
# window: warm-up is supposed to have compiled every program.
WATCHDOG_MS = 1000


class SmokeFailure(AssertionError):
    """A phase ran to the end and what came out is wrong."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """Per-phase set-up time from the events JAX itself records around
    every backend compile: seconds spent in compile-or-load
    (`compile_s`), the part of that spent reading the persistent cache
    (`cache_read_s`), what the hits saved by JAX's own account
    (`compile_saved_s`), and the cache's hits and writes."""

    EVENTS = {
        "/jax/core/compile/backend_compile_duration": "compile_s",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
        "/jax/compilation_cache/compile_time_saved_sec": "compile_saved_s",
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_writes",
    }

    def __init__(self):
        from jax import monitoring

        self.totals = dict.fromkeys(self.EVENTS.values(), 0)
        monitoring.register_event_duration_secs_listener(self._add)
        monitoring.register_event_listener(self._add)

    def _add(self, event, seconds=1, **_):
        if event in self.EVENTS:
            self.totals[self.EVENTS[event]] += seconds

    def take(self) -> dict:
        out = {k: round(v, 2) for k, v in self.totals.items()}
        self.totals = dict.fromkeys(self.totals, 0)
        return out


def run_cli(argv: list[str], jsonl: Path) -> dict[str, list[dict]]:
    """One in-process CLI run; its records, grouped by event. The CLI's
    own stdout (bench summaries) goes to stderr: stdout carries only
    this script's stamped lines."""
    from mpi_cuda_cnn_tpu.cli import main as cli_main
    from mpi_cuda_cnn_tpu.obs.schema import load_records

    jsonl.parent.mkdir(parents=True, exist_ok=True)
    jsonl.unlink(missing_ok=True)
    with contextlib.redirect_stdout(sys.stderr):
        rc = cli_main([*argv, "--metrics-jsonl", str(jsonl)])
    check(rc == 0, f"cli exited {rc}: {' '.join(argv)}")
    by_event: dict[str, list[dict]] = {}
    for rec in load_records(jsonl):
        by_event.setdefault(rec["event"], []).append(rec)
    first = next(iter(by_event), None)
    check(first == "device",
          f"first record is {first!r}, not the device stamp")
    return by_event


def check_data_parallel(recs: dict, ndev: int) -> dict:
    """What a >1-chip run must show, from the run's own records: the
    default mesh spans every chip, every chip holds bytes, and the
    compiled step reduces across them."""
    mesh = recs["device"][0]["mesh"]
    check(mesh == {"data": ndev}, f"mesh {mesh}, want data:{ndev}")
    collectives: dict[str, int] = {}
    for p in recs.get("program", []):
        for name, n in p["collectives"].items():
            collectives[name] = collectives.get(name, 0) + n
    in_use = [d["stats"]["bytes_in_use"] if d["stats"] else None
              for d in recs["memory"][-1]["devices"]] \
        if recs.get("memory") else []
    if ndev > 1:
        check(collectives.get("all-reduce", 0) >= 1,
              f"no all-reduce in the compiled step: {collectives}")
        if recs["device"][0]["platform"] != "cpu":  # cpu has no stats
            check(len(in_use) == ndev and all(in_use),
                  f"bytes_in_use per device: {in_use}")
    return {"mesh": mesh, "collectives": collectives,
            "bytes_in_use": in_use}


# ------------------------------------------------------------- phases


def phase_kernels(cfg, dev, rehearsal):
    """Each Pallas kernel vs its XLA twin, on the device. The error
    measure is max|got - want| / max|want| — absolute error normalized
    by the output's scale, so near-zero entries don't dominate."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mpi_cuda_cnn_tpu.models.generate import _quant_kv
    from mpi_cuda_cnn_tpu.ops.attention import attention
    from mpi_cuda_cnn_tpu.ops.pallas_attention import flash_attention
    from mpi_cuda_cnn_tpu.ops.pallas_gemv import (
        dequantize_weight,
        int8_gemv,
        quantize_weight,
    )
    from mpi_cuda_cnn_tpu.serve import paged_cache

    # Tolerances on max|got - want| / max|want|, each with its reason.
    # Every f32 bound must still fail a bf16 computation of the same
    # case, whose operand rounding alone is 2^-9 = 2e-3 (measured on
    # this chip: a default-precision f32 dot, which rounds its operands
    # to bf16, is 2.5e-3 off; at HIGHEST it is 3e-7 — PERF.md).
    #
    # the bounded paged read over int8 rows and the int8 GEMV: both
    # sides compute to f32 accuracy (the read and its twin both under
    # "highest", the GEMV with x's three exact bf16 terms against the
    # exact bf16 weight tile), so only reduction order differs — a few
    # 1e-7 over <= 16k-term sums. 2e-5 leaves two orders of margin.
    F32_TOL = 2e-5
    # f32 flash: the kernels rebuild p = exp(s - lse) from HIGHEST-
    # precision logits, and exp turns an absolute logit error (~1e-6 at
    # |s| ~ 10) into a relative error in p that the backward multiplies
    # by (dO.V - D), a cancelling difference. The kernel's documented
    # gradient accuracy is ~4e-5 (ops/pallas_attention.py); 2e-4 is 5x
    # that and still 10x below the bf16 floor.
    FLASH_F32_TOL = 2e-4
    # bf16 flash (the LM's training dtype): inputs are bf16 on both
    # sides; the kernel additionally rounds the probabilities to bf16
    # for the PV dot and its output to bf16 (2^-9 each), and the
    # backward differentiates through both. 2e-2 is ~5 bf16 roundings.
    FLASH_BF16_TOL = 2e-2

    errs: dict[str, float] = {}
    over: list[str] = []

    def compare(name, got, want, tol):
        """Record the error; violations are collected, not raised, so a
        failing run still reports every kernel's number."""
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(np.isfinite(got).all(), f"{name}: non-finite kernel output")
        e = errs[name] = float(np.max(np.abs(got - want))
                               / np.max(np.abs(want)))
        if e > tol:
            over.append(f"{name}: rel err {e:.2e} > {tol}")

    def mosaic(fn, *args):
        """On the chip the lowered program must hold a Mosaic custom
        call; in rehearsal the same kernel body runs interpreted."""
        if rehearsal:
            return
        text = jax.jit(fn).lower(*args).as_text()
        check("tpu_custom_call" in text,
              "no Mosaic custom call in the lowered program")

    def twin(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    rng = np.random.default_rng(0)
    lm, sv = cfg["lm"], cfg["serve"]
    hd = lm["dim"] // lm["heads"]

    # Flash forward + both backward kernels at the LM step's shape.
    shape = (lm["per_chip_batch"], lm["seq"], lm["heads"], hd)
    for dtype, tol in (("float32", FLASH_F32_TOL),
                       ("bfloat16", FLASH_BF16_TOL)):
        q, k, v = (jnp.asarray(rng.normal(size=shape), dtype)
                   for _ in range(3))

        def fwd_bwd(attn):
            def f(q, k, v):
                out, vjp = jax.vjp(attn, q, k, v)
                # A fixed non-uniform cotangent (the same on both
                # sides) drives the dq and the dk/dv kernels.
                g = jnp.cos(3.0 * q.astype(jnp.float32)).astype(out.dtype)
                return (out, *vjp(g))
            return f

        flash = fwd_bwd(lambda q, k, v: flash_attention(q, k, v, True))
        oracle = fwd_bwd(lambda q, k, v: attention(q, k, v, causal=True))
        mosaic(flash, q, k, v)
        got, want = jax.jit(flash)(q, k, v), twin(oracle, q, k, v)
        for name, g, w in zip(("o", "dq", "dk", "dv"), got, want):
            compare(f"flash_{dtype}_{name}", g, w, tol)

    # The bounded paged read (serve/paged_cache.bounded_read, its loop
    # forced where the table is so small that the code reads it whole)
    # against the whole-table gather + attend_kv, at the benchmark's
    # two head layouts and pool types (chat: MHA rows in bf16;
    # generation: MQA rows in int8) and the engine's two program shapes
    # (decode tick with dead slots between live ones, prefill chunk).
    # Both under "highest", where only the order of the softmax's sums
    # differs; then the read as the engine runs it (default precision:
    # the MXU rounds an f32 operand to bf16) against the same twin.
    for name, lay in sv["paged"].items():
        h, hkv, ps, dtype = sv["heads"], lay["kv_heads"], sv["page_size"], \
            lay["cache_dtype"]
        hd = sv["dim"] // h
        per = -(-lay["max_len"] // ps)
        pool = lay["slots"] * per + 1
        rows = jnp.asarray(rng.normal(size=(2, 1, pool * ps, hkv, hd)),
                           jnp.float32)
        if dtype == "int8":
            (qk, sk), (qv, sv_) = _quant_kv(rows[0]), _quant_kv(rows[1])
            c = {"k": qk.reshape(pool, ps, hkv, hd),
                 "ks": sk.reshape(pool, ps, hkv, 1),
                 "v": qv.reshape(pool, ps, hkv, hd),
                 "vs": sv_.reshape(pool, ps, hkv, 1)}
        else:
            c = {"k": rows[0].reshape(pool, ps, hkv, hd).astype(dtype),
                 "v": rows[1].reshape(pool, ps, hkv, hd).astype(dtype)}
        del rows
        loop = (max(1, min(per // 4, 128 // ps)), 4)
        for b, kk in ((lay["slots"], 1), (1, sv["prefill_chunk"])):
            q = jnp.asarray(rng.normal(size=(b, kk, h, hd)), jnp.float32)
            k, v = (jnp.asarray(rng.normal(size=(b, kk, hkv, hd)),
                                jnp.float32) for _ in range(2))
            # Every third row of the tick is dead: position 0, not
            # valid, an all-scratch table.
            live = np.arange(b) % 3 != 1
            table = np.where(live[:, None], np.stack([
                rng.choice(np.arange(1, pool), per, replace=False)
                for _ in range(b)]), 0).astype(np.int32)
            pos0 = rng.integers(0, per * ps - kk + 1, (b, 1)) * live[:, None]
            positions = jnp.asarray(pos0 + np.arange(kk), jnp.int32)
            valid = jnp.asarray(np.broadcast_to(live[:, None], (b, kk)))
            table = jnp.asarray(table)

            def read(step):
                def f(c, q, k, v):
                    with mock.patch.object(paged_cache, "read_step",
                                           lambda *a, **k: step):
                        return paged_cache.paged_update_attend(
                            c, q, k, v, positions, valid, table, ps)[0]
                return f

            want = twin(read((per, b)), c, q, k, v)
            # bf16 rows: the loop rounds exp(s - block max) to bf16 for
            # the second product where the gather rounds the normalised
            # probabilities: 2^-8 a term, twice that on the output.
            tol = 2 * 2.0 ** -8 if dtype == "bfloat16" else F32_TOL
            compare(f"paged_{name}_b{b}_kk{kk}",
                    twin(read(loop), c, q, k, v), want, tol)
            # As served: one MXU pass rounds f32 queries and
            # probabilities to bf16 (2^-9 each) on either form.
            compare(f"paged_{name}_b{b}_kk{kk}_as_served",
                    jax.jit(read(loop))(c, q, k, v), want, 2e-2)

    # The same for the latent layout (bounded_read_latent against the
    # whole-table gather + attend_latent) at the `dots` cell's shape:
    # on the chip the loop at the step the code itself picks there (it
    # must engage: fewer rows read than the table holds), in rehearsal
    # forced. bf16 rows and weights: beside the probabilities' rounding
    # (exp(s - block max) in the loop, normalised in the gather: 2^-8 a
    # term) both forms round the weighted latents to bf16 before `wuv`,
    # so the tolerance is twice the K/V one, 4 x 2^-8 = 1.6e-2.
    from mpi_cuda_cnn_tpu.models.transformer import LatentAttn

    lat, ps = sv["latent"], sv["page_size"]
    a = LatentAttn(q_rank=lat["kv_rank"], kv_rank=lat["kv_rank"],
                   nope=lat["nope"], rope=lat["rope"], v=lat["v"])
    h, dtype = lat["heads"], lat["cache_dtype"]
    per = -(-lat["max_len"] // ps)
    pool = lat["slots"] * per + 1
    rows = np.zeros((pool * ps, lat["lanes"]), np.float32)
    rows[:, :a.row] = rng.normal(size=(pool * ps, a.row))
    c = {"c": jnp.asarray(rows, dtype).reshape(pool, ps, -1)}
    blk = {"wuk": jnp.asarray(rng.normal(size=(h, a.nope, a.kv_rank))
                              / np.sqrt(a.nope), dtype),
           "wuv": jnp.asarray(rng.normal(size=(h, a.kv_rank, a.v))
                              / np.sqrt(a.kv_rank), dtype)}
    del rows
    for b, kk in ((lat["slots"], 1), (1, sv["prefill_chunk"])):
        q = jnp.asarray(rng.normal(size=(b, kk, h, a.nope + a.rope)), dtype)
        row = jnp.asarray(rng.normal(size=(b, kk, 1, a.row)), dtype)
        live = np.arange(b) % 3 != 1
        table = jnp.asarray(np.where(live[:, None], np.stack([
            rng.choice(np.arange(1, pool), per, replace=False)
            for _ in range(b)]), 0).astype(np.int32))
        # The tick's slots at any depth; the chunk mid-prompt.
        pos0 = (rng.integers(0, per * ps, (b, 1)) * live[:, None] if kk == 1
                else np.full((b, 1), per * ps // 2 - kk))
        positions = jnp.asarray(pos0 + np.arange(kk), jnp.int32)
        valid = jnp.asarray(np.broadcast_to(live[:, None], (b, kk)))

        def read(step):
            def f(c, q, row, blk):
                forced = (mock.patch.object(paged_cache, "read_step",
                                            lambda *a, **k: step)
                          if step else contextlib.nullcontext())
                with forced:
                    o, _, n = paged_cache.paged_update_attend_latent(
                        c, q, row, positions, valid, table, ps, blk, a)
                return o, n
            return f

        loop = (max(1, per // 4), 3) if rehearsal else None
        want, _ = twin(read((per, b)), c, q, row, blk)
        got, n = twin(read(loop), c, q, row, blk)
        check(int(n) < b * per * ps,
              f"latent b{b} kk{kk}: the read touched {int(n)} rows of a "
              f"table of {b * per * ps}: the loop did not engage")
        compare(f"paged_latent_b{b}_kk{kk}", got[live], want[live],
                4 * 2.0 ** -8)
        compare(f"paged_latent_b{b}_kk{kk}_as_served",
                jax.jit(read(loop))(c, q, row, blk)[0][live], want[live],
                2e-2)

    # `minicpm-sala`'s two mixers at the cell's layout (32 slots of
    # 65,536 rows, 2 K/V heads of 128 in bf16; a tick and a 512-row
    # chunk of 32 heads). The SELECTED READ: a tick walks the union of
    # its K/V heads' chosen blocks, a chunk walks every block to its
    # depth under the selection's mask, both against the plain twin --
    # the whole table gathered and attend_kv under the same mask, the
    # chunk's queries 128 rows at a time so that its scores fit. The
    # blocks are chosen once (select_blocks, as served) and handed to
    # both sides: what is held here is the read, the selection is held
    # to the reference's on the CPU (tests/test_sparse_linear.py).
    # bf16 rows: as the K/V layouts above, 2 x 2^-8 under "highest",
    # 2e-2 as served. The CHUNKED LINEAR PRODUCT (generate.
    # linear_attend) against the token recurrence S = l S + k^T v, o =
    # q S, one row at a time in f32 at "highest": under "highest" the
    # chunk form rounds its decayed scores to the values' type (bf16:
    # 2^-9 a term, 2^-8 on a sum of such terms and the state's share
    # beside it), as served the MXU rounds q and k too: 2e-2.
    from mpi_cuda_cnn_tpu.models.generate import linear_attend
    from mpi_cuda_cnn_tpu.models.transformer import LinearAttn, SparseSelect

    sp, ps = sv["sparse"], sv["page_size"]
    sel = SparseSelect(**sp["select"])
    h, hkv, hd, dtype = (sp["heads"], sp["kv_heads"], sp["head_dim"],
                         sp["cache_dtype"])
    per = -(-sp["max_len"] // ps)
    pool = sp["slots"] * per + 1
    c = {n: jnp.asarray(rng.standard_normal(
        (pool, ps, hkv, hd), np.float32), dtype) for n in ("k", "v")}
    kc = jnp.asarray(rng.standard_normal(
        (pool, ps // sel.stride, hkv, hd), np.float32), dtype)
    for b, kk in ((sp["slots"], 1), (1, sp["chunk"])):
        q = jnp.asarray(rng.normal(size=(b, kk, h, hd)), dtype)
        live = np.arange(b) % 3 != 1
        table = jnp.asarray(np.where(live[:, None], np.stack([
            rng.choice(np.arange(1, pool), per, replace=False)
            for _ in range(b)]), 0).astype(np.int32))
        # The tick's slots at any depth, most past dense_len; the chunk
        # at the table's far end.
        pos0 = (rng.integers(0, per * ps, (b, 1)) * live[:, None] if kk == 1
                else np.full((b, 1), per * ps - kk))
        positions = jnp.asarray(pos0 + np.arange(kk), jnp.int32)
        valid = jnp.asarray(np.broadcast_to(live[:, None], (b, kk)))
        chosen, _, _, nchosen = jax.jit(
            lambda q, kc: paged_cache.select_blocks(
                q, kc, positions, valid, table, ps, sel))(q, kc)
        nb = chosen.shape[-1]
        check(0 < int(nchosen) < int(np.sum(live)) * kk * hkv * nb,
              f"sparse b{b} kk{kk}: {int(nchosen)} blocks chosen")
        walk = None
        step = paged_cache.read_step(b, per, ps, 2 * hkv * hd * 2)
        if rehearsal:
            step = (max(1, sel.block // ps), 3)
        if kk == 1:
            walk, step = paged_cache.chosen_walk(chosen, sel, step, ps)

        def read(c, q):
            return paged_cache.bounded_read(
                q, c, positions, valid, table, chosen, walk, page_size=ps,
                step=step, sel_block=sel.block)

        def whole(c, q):
            pieces = [paged_cache.bounded_read(
                q[:, i:i + 128], c, positions[:, i:i + 128],
                valid[:, i:i + 128], table, chosen[:, :, i:i + 128],
                page_size=ps, step=(per, b), sel_block=sel.block)[0]
                for i in range(0, kk, 128)]
            return jnp.concatenate(pieces, axis=1)

        want = twin(whole, c, q)
        got, n = twin(read, c, q)
        if kk == 1:
            check(int(n) < int(np.sum(pos0 + 1)),
                  f"sparse tick: the walk touched {int(n)} rows, the slots "
                  f"hold {int(np.sum(pos0 + 1))}: no block was skipped")
        compare(f"sparse_read_b{b}_kk{kk}", got[live], want[live],
                2 * 2.0 ** -8)
        compare(f"sparse_read_b{b}_kk{kk}_as_served",
                jax.jit(read)(c, q)[0][live], want[live], 2e-2)
    del c, kc

    ld = LinearAttn().log_decay(h)

    def recurrence(q, k, v, state, valid):
        def one(s, row):
            qt, kt, vt, ok = row                     # (B, H, hd), (B,)
            new = jnp.exp(ld)[None, :, None, None] * s + jnp.einsum(
                "bhd,bhe->bhde", kt, vt)
            s = jnp.where(ok[:, None, None, None], new, s)
            return s, jnp.einsum("bhd,bhde->bhe", qt, s) / np.sqrt(hd)
        f32 = lambda x: jnp.swapaxes(x.astype(jnp.float32), 0, 1)  # noqa: E731
        s, o = jax.lax.scan(one, state, (f32(q), f32(k), f32(v), valid.T))
        return jnp.swapaxes(o, 0, 1).reshape(q.shape[0], q.shape[1], -1), s

    for b, kk in ((sp["slots"], 1), (1, sp["chunk"])):
        q, k, v = (jnp.asarray(rng.normal(size=(b, kk, h, hd)), dtype)
                   for _ in range(3))
        state = jnp.asarray(rng.normal(size=(b, h, hd, hd)), jnp.float32)
        # The chunk's last rows are padding; every third slot of the
        # tick is dead.
        valid = jnp.asarray(np.arange(kk)[None, :] < kk - kk // 8 if kk > 1
                            else (np.arange(b) % 3 != 1)[:, None])
        want_o, want_s = twin(recurrence, q, k, v, state, valid)
        form = lambda q, k, v, state: linear_attend(  # noqa: E731
            q, k, v, state, valid, ld)
        keep = np.asarray(valid)
        for tag, (o, s_), tol in (
                ("", twin(form, q, k, v, state), 2.0 ** -8),
                ("_as_served", jax.jit(form)(q, k, v, state), 2e-2)):
            compare(f"linear_b{b}_kk{kk}{tag}", np.asarray(o)[keep],
                    np.asarray(want_o)[keep], tol)
            compare(f"linear_state_b{b}_kk{kk}{tag}", s_, want_s, tol)

    # The expert layer's two forms at `smallthinker`'s layout: a chunk's
    # sorted pairs through the kernel (ops/pallas_expert_mlp) against
    # the walk in 128-row steps of lax.ragged_dot, one draw from a plain
    # softmax router and one skewed (half the experts get most of the
    # pairs, some none). The rule must pick the kernel by itself; the
    # walk is forced. Both put bf16 rows and matrices through the MXU
    # with f32 accumulation and round the hidden rows to bf16 before
    # `wd`: only the order of the f32 sums differs, which can move a
    # hidden value by one bf16 step, 2^-8 of it, and the output by less
    # of its largest: tolerance 2^-8. The walk has no reading under
    # "highest" (XLA's grouped kernel refuses bf16 at that precision:
    # "Bad lhs type"), so the twin under "highest" is the plain product,
    # every row through every expert in f32 with the hidden rows
    # rounded as the program rounds them; the layer's bf16 OUTPUT is
    # half a step, 2^-9, from it by its own rounding (2.0e-3 to 2.4e-3
    # in rehearsal): tolerance 2^-7 there, four such half steps.
    from mpi_cuda_cnn_tpu.models.transformer import RoutedExperts
    from mpi_cuda_cnn_tpu.parallel import ep

    ex = sv["experts"]
    spec = RoutedExperts(experts=ex["experts"], top_k=ex["top_k"],
                         held=tuple(range(ex["experts"])), router="softmax",
                         act="relu", reads="layer_input")
    d, wd_ = ex["dim"], ex["width"]
    bank = {m: jnp.asarray(rng.normal(size=(ex["experts"], *shape))
                           / np.sqrt(shape[0]), "bfloat16")
            for m, shape in (("wg", (d, wd_)), ("wu", (d, wd_)),
                             ("wd", (wd_, d)))}
    gate = jnp.asarray(rng.normal(size=(d, ex["experts"])) / np.sqrt(d),
                       jnp.float32)
    blk = {"experts": bank, "router": {"gate": gate}}
    check(ep.tiled_products(ex["rows"], spec, bank),
          f"experts: {ex['rows']} rows x top-{ex['top_k']} did not take "
          "the kernel")
    drawn = {}      # [pairs, experts hit, largest load] a draw
    for draw, lift in (("uniform", 0.0), ("skewed", 3.0)):
        x = jnp.asarray(rng.normal(size=(ex["rows"], d)), "bfloat16")
        logits = (x.astype(jnp.float32) @ gate
                  + lift * jnp.linspace(1.0, -1.0, ex["experts"]))
        w, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), ex["top_k"])
        routing = (ids.astype(jnp.int32), w / jnp.sum(w, -1, keepdims=True))

        def layer(x, kernel):
            if kernel:
                return ep.moe_held_inference(x, blk, spec, routing=routing)
            with mock.patch.object(ep, "tiled_products", lambda *a: False):
                return ep.moe_held_inference(x, blk, spec, routing=routing)

        def plain(x):
            xf, (ids, w) = x.astype(jnp.float32), routing

            def one(e, y):
                g, u, dn = (bank[m][e].astype(jnp.float32)
                            for m in ("wg", "wu", "wd"))
                h = (jax.nn.relu(xf @ g) * (xf @ u)).astype(x.dtype)
                return y + jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1,
                                   keepdims=True) * (
                    h.astype(jnp.float32) @ dn)

            return jax.lax.fori_loop(0, ex["experts"], one,
                                     jnp.zeros(x.shape, jnp.float32))

        mosaic(lambda x: layer(x, True)[0], x)
        walked, counts = jax.jit(lambda x: layer(x, False))(x)
        got, same = jax.jit(lambda x: layer(x, True))(x)
        check(np.array_equal(counts, same) and int(counts[0])
              == ex["rows"] * ex["top_k"],
              f"experts {draw}: counts {counts} / {same}")
        drawn[draw] = [int(c) for c in counts]
        compare(f"experts_{draw}", got, twin(plain, x), 2.0 ** -7)
        compare(f"experts_{draw}_as_served", got, walked, 2.0 ** -8)

    # int8 GEMV at the decode tick's widest matrices: the MLP pair
    # (w2's din = 4*dim is the contraction that overflowed VMEM untiled).
    dim = sv["dim"]
    for n, din, dout in ((sv["slots"], dim, 4 * dim),
                         (sv["slots"], 4 * dim, dim),
                         (sv["prefill_chunk"], 4 * dim, dim)):
        x = jnp.asarray(rng.normal(size=(n, din)), jnp.float32)
        w = quantize_weight(
            jnp.asarray(rng.normal(size=(din, dout)), jnp.float32))
        mosaic(int8_gemv, x, w)
        compare(f"gemv_n{n}_{din}x{dout}", jax.jit(int8_gemv)(x, w),
                twin(lambda x, w: x @ dequantize_weight(w), x, w), F32_TOL)

    rounded = {k: float(f"{v:.2e}") for k, v in errs.items()}
    check(not over, f"{'; '.join(over)} (all: {rounded})")
    return {"max_rel_err": rounded, "mosaic_checked": not rehearsal,
            "experts_drawn": drawn}


def phase_cnn(cfg, dev, rehearsal):
    from mpi_cuda_cnn_tpu.data.datasets import (
        synthetic_stripes,
        write_synthetic_idx,
    )

    c = cfg["cnn"]
    ndev = dev["device_count"]
    paths = write_synthetic_idx(
        IDX, synthetic_stripes(num_train=c["train"], num_test=c["test"]))
    recs = run_cli(
        [str(paths[k]) for k in ("train_images", "train_labels",
                                 "test_images", "test_labels")]
        + ["--model", "reference_cnn", "--epochs", "2", "--batch-size",
           str(c["per_chip_batch"] * ndev), "--device", dev["platform"]],
        OUT / "cnn.jsonl")
    ev = recs["eval"][-1]
    acc = ev["ncorrect"] / ev["ntests"]
    check(ev["ntests"] == c["test"], f"ntests {ev['ntests']}")
    check(acc >= 0.99, f"accuracy {ev['ncorrect']}/{ev['ntests']} < 0.99")
    flops = [p["flops"] for p in recs.get("program", [])]
    check(flops and all(flops), f"program-cost flops missing: {flops}")
    return {"ncorrect": ev["ncorrect"], "ntests": ev["ntests"],
            "epoch_s": [round(e["seconds"], 3) for e in recs["epoch"]],
            "step_flops": flops[0], **check_data_parallel(recs, ndev)}


def phase_lm(cfg, dev, rehearsal):
    import math

    c = cfg["lm"]
    ndev = dev["device_count"]
    recs = run_cli(
        ["lm", "--corpus", "synthetic", "--dim", str(c["dim"]),
         "--depth", str(c["depth"]), "--heads", str(c["heads"]),
         "--seq-len", str(c["seq"]),
         "--batch-size", str(c["per_chip_batch"] * ndev),
         "--compute-dtype", "bfloat16", "--steps", str(c["steps"]),
         # Six steps must show a falling loss: no warm-up ramp.
         "--lr-schedule", "constant", "--warmup-steps", "0",
         "--log-every", "1", "--device", dev["platform"]],
        OUT / "lm.jsonl")
    # On the chip "auto" must resolve to the fused kernel at this
    # 128-aligned bf16 shape; on the CPU the oracle is the deliberate
    # pick (train/lm.pick_attn_impl).
    attn = recs["device"][0]["attn"]
    want_attn = "oracle" if rehearsal else "flash"
    check(attn == want_attn, f"attn resolved to {attn!r}, want {want_attn!r}")
    losses = [r["loss"] for r in recs["train"]]
    check(len(losses) == c["steps"], f"{len(losses)} train records")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return {"attn": attn, "losses": [round(x, 4) for x in losses],
            **check_data_parallel(recs, ndev)}


def phase_serve(cfg, dev, rehearsal, extra=(), tag="default"):
    c = cfg["serve"]
    recs = run_cli(
        ["serve-bench", "--mode", "continuous", "--dim", str(c["dim"]),
         "--depth", str(c["depth"]), "--heads", str(c["heads"]),
         "--max-seq", str(c["max_seq"]), "--slots", str(c["slots"]),
         "--page-size", str(c["page_size"]),
         "--prefill-chunk", str(c["prefill_chunk"]),
         "--prompt-max", str(c["prompt_max"]),
         "--out-max", str(c["out_max"]),
         "--requests", str(c["requests"]), "--rate", "0",
         "--watchdog-ms", str(WATCHDOG_MS),
         "--device", dev["platform"], *extra],
        OUT / f"serve_{tag}.jsonl")
    s = recs["serve"][-1]
    n = c["requests"]
    check(s["statuses"] == {"finished": n}, f"statuses {s['statuses']}")
    reqs = recs["request"]
    check(len(reqs) == n and all(r["output_tokens"] > 0 for r in reqs),
          f"output tokens per request: "
          f"{[r['output_tokens'] for r in reqs]}")
    check(s["watchdog_slow_ticks"] == 0,
          f"{s['watchdog_slow_ticks']} ticks slower than {WATCHDOG_MS} ms "
          "(a compile inside the serving window?)")
    return {k: s[k] for k in ("cache_dtype", "weights_dtype",
                              "output_tokens", "decode_ticks",
                              "prefill_chunks", "duration_s")}


def phase_serve_config(cfg, dev, rehearsal):
    """README's serving configuration: GQA, auto cache/weights dtypes
    (int8 both under GQA)."""
    return phase_serve(
        cfg, dev, rehearsal, tag="serving_config",
        extra=["--kv-heads", str(cfg["serve"]["kv_heads"]),
               "--cache-dtype", "auto", "--decode-weights-dtype", "auto"])


PHASES = (
    # Kernels first: a broken kernel fails here, with its number,
    # before the phases that reach it.
    ("kernels", phase_kernels),
    ("cnn", phase_cnn),
    ("lm", phase_lm),
    ("serve_default", phase_serve),
    ("serve_serving_config", phase_serve_config),
)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy shapes on the CPU (sandbox, tier-1); never "
                         "reports a chip run")
    args = ap.parse_args(argv)

    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    d0 = jax.devices()[0]
    dev = {"platform": d0.platform, "device_kind": d0.device_kind,
           "device_count": len(jax.devices())}
    if not args.rehearse and dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX found platform "
              f"{dev['platform']!r}); nothing was compiled. "
              "`--rehearse` runs toy shapes on the CPU.", file=sys.stderr)
        return 2

    from mpi_cuda_cnn_tpu.utils.backend import enable_compile_cache

    stamp = dict(dev, rehearsal=True) if args.rehearse else dev

    def emit(**fields):
        print(json.dumps({**fields, **stamp}), flush=True)

    emit(event="start", compile_cache=enable_compile_cache(),
         jax=jax.__version__)
    # Keep every compiled program, not only those that took over a
    # second: the tool's machine is cold on every call and even a tiny
    # program costs the TPU compiler ~0.1 s, so a second run in the same
    # call shows what the cache can save.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = CompileMeter()
    cfg = TOY if args.rehearse else FULL
    failed = []
    for name, fn in PHASES:
        t0 = time.perf_counter()
        # The boundary that keeps the other phases running: a failure is
        # printed with its traceback, recorded in the phase line, and
        # makes the exit code non-zero — it is never swallowed.
        try:
            detail = {"ok": True, **fn(cfg, dev, args.rehearse)}
        except Exception as e:
            traceback.print_exc()
            failed.append(name)
            detail = {"ok": False, "error": f"{type(e).__name__}: {e}"[:2000]}
        emit(event="phase", phase=name,
             wall_s=round(time.perf_counter() - t0, 2),
             **meter.take(), **detail)

    # The last line, to the driver's contract: on a passing chip run it
    # is exactly {"ok": true, "device": {platform, kind, count}}. A
    # rehearsal also carries the stamp every other line does.
    summary = {"ok": not failed,
               "device": {"platform": dev["platform"],
                          "kind": dev["device_kind"],
                          "count": dev["device_count"]}}
    if failed:
        summary["failed"] = failed
    if args.rehearse:
        summary.update(stamp)
    print(json.dumps(summary), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
