"""KV-cache decode benchmark: prefill + steady-state generation tokens/s.

The training benches (bench_lm.py) measure the MXU-bound step; decode is
the other regime — one token per forward, bound by reading the KV cache
and weights from HBM. This bench times models/generate.py's real product
path (prefill -> jitted decode scan) and shows the GQA effect: the cache
is (B, max_seq, Hkv, D), so kv_heads < heads cuts cache reads by
heads/kv_heads — the reason serving stacks use GQA (generate.init_cache).

ISSUE 12 axes: `--paged` switches to the PAGED cache (identity block
tables over a page pool — the serving layout; its read is
serve/paged_cache.bounded_read); `--weights-dtype int8` turns on the
per-channel quantized decode GEMVs (ops/pallas_gemv, quantized once
before timing). Every paged row carries the greedy token CRC of its
seeded workload.

Timing: a generate(num_tokens=N) run costs fixed dispatch + prefill +
N * per_token; timing N and 2N and reporting (T2N - TN)/N cancels the
fixed and prefill parts exactly, leaving the steady-state per-token
decode cost (the same two-point method as scripts/bench_lm.py).
Prefill is timed separately on its own jitted function, also two-point
(loops of n and 2n calls). Completion is forced with
jax.block_until_ready (it waits on this machine — utils/sync.py).

Output: one schema `bench` record per config row (metric + value + unit
— `mctpu compare` reads every row) plus the headline record.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import zlib

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mpi_cuda_cnn_tpu.models.generate import decode_step, generate, prefill
from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
from mpi_cuda_cnn_tpu.obs.schema import make_record
from mpi_cuda_cnn_tpu.ops.pallas_gemv import quantize_decode_params
from mpi_cuda_cnn_tpu.train.lm import count_params
from mpi_cuda_cnn_tpu.utils.backend import claim_device
from mpi_cuda_cnn_tpu.utils.sync import two_point

_T0 = time.perf_counter()


def _emit(metric, value, unit, **fields):
    """One schema-stamped `bench` row (ISSUE 12 satellite: every row a
    schema record with unit, so `mctpu compare` gates any of them)."""
    print(json.dumps(make_record(
        "bench", time.perf_counter() - _T0,
        metric=metric, value=value, unit=unit, **fields,
    )))


def bench_decode_config(model, *, batch, prompt_len, gen_tokens,
                        cache_dtype="float32", weights_dtype="float32",
                        seed=0):
    params = quantize_decode_params(
        model.init(jax.random.key(seed)), weights_dtype)
    rng = np.random.default_rng(seed)
    prompt = jnp.asarray(
        rng.integers(0, model.vocab, (batch, prompt_len)), jnp.int32
    )

    def timed_gen(n):
        t0 = time.perf_counter()
        toks = generate(model, params, prompt, n, cache_dtype=cache_dtype)
        jax.block_until_ready(toks)
        return time.perf_counter() - t0

    # Warm both compile-cache entries (generate() compiles per n), then
    # the shared two-point core: window cancellation + median-of-3.
    timed_gen(gen_tokens)
    timed_gen(2 * gen_tokens)
    per_tok = two_point(timed_gen, gen_tokens, warmup=0)

    # Prefill alone (jitted once here; generate()'s fused program includes
    # it, which is exactly why the two-point difference above excludes it).
    cdt = jnp.dtype(cache_dtype)
    pf = jax.jit(lambda p, t: prefill(model, p, t, cache_dtype=cdt)[0])
    jax.block_until_ready(pf(params, prompt))

    def timed_pf(loops):
        t0 = time.perf_counter()
        for _ in range(loops):
            out = pf(params, prompt)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    prefill_s = two_point(timed_pf, 4, warmup=0)
    return per_tok, prefill_s


@functools.lru_cache(maxsize=16)
def _compiled_paged_run(model, s0: int, num_tokens: int, batch: int,
                        cache_dtype: str, page_size: int):
    """One jitted paged prefill-block + greedy decode scan per config:
    the paged twin of generate()'s program, driven through the SAME
    decode_step dispatch the engine uses (PagedKVCache with per-slot
    positions), over identity block tables sized to s0 + num_tokens."""
    import dataclasses

    from mpi_cuda_cnn_tpu.serve.paged_cache import (
        init_paged_cache,
        pages_for,
    )

    cdt = jnp.dtype(cache_dtype)
    max_len = s0 + num_tokens
    per = pages_for(max_len, page_size)
    table = 1 + np.arange(batch * per, dtype=np.int32).reshape(batch, per)

    @jax.jit
    def run(params, prompt):
        from mpi_cuda_cnn_tpu.models.generate import decode_block

        cache = init_paged_cache(
            model, slots=batch, num_pages=batch * per + 1,
            page_size=page_size, dtype=cdt, max_len=max_len,
        )
        cache = dataclasses.replace(cache, block_table=jnp.asarray(table))
        # Paged prefill: the whole prompt as one cached block forward
        # (teacher-forced writes, causal reads — decode_block's k>1
        # form), then the greedy decode scan at per-slot positions.
        logits, cache = decode_block(
            model, params, prompt, jnp.zeros((batch,), jnp.int32), cache
        )
        logits = logits[:, -1, :]

        def body(carry, i):
            cache, logits = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nl, cache = decode_step(
                model, params, tok, jnp.full((batch,), s0 + i, jnp.int32),
                cache,
            )
            return (cache, nl), tok

        (_, logits), toks = jax.lax.scan(
            body, (cache, logits), jnp.arange(num_tokens - 1)
        )
        last = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.concatenate([toks, last[None, :]], axis=0).T

    return run


def bench_paged_config(model, *, batch, prompt_len, gen_tokens,
                       cache_dtype, weights_dtype, page_size, seed=0):
    """Two-point paged decode timing + the greedy token CRC of the
    seeded workload."""
    params = quantize_decode_params(
        model.init(jax.random.key(seed)), weights_dtype)
    rng = np.random.default_rng(seed)
    prompt = jnp.asarray(
        rng.integers(0, model.vocab, (batch, prompt_len)), jnp.int32
    )

    def timed(n):
        run = _compiled_paged_run(model, prompt_len, n, batch,
                                  cache_dtype, page_size)
        t0 = time.perf_counter()
        toks = run(params, prompt)
        jax.block_until_ready(toks)
        return time.perf_counter() - t0

    # Warm the N-program AND capture its tokens for the CRC in one run
    # (greedy decode is deterministic — a ninth decode purely for the
    # CRC would be wasted wall-clock on the interpret path).
    run = _compiled_paged_run(model, prompt_len, gen_tokens, batch,
                              cache_dtype, page_size)
    toks = np.asarray(run(params, prompt), np.int32)
    timed(2 * gen_tokens)
    per_tok = two_point(timed, gen_tokens, warmup=0)
    crc = zlib.crc32(toks.tobytes())
    return per_tok, crc, toks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--kv-heads", type=str, default="0,2,1",
                    help="comma list; 0 = MHA, else GQA/MQA cache sizes")
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--max-seq", type=int, default=2048)
    ap.add_argument("--prompt", type=int, default=1024)
    ap.add_argument("--tokens", type=int, default=256,
                    help="N for the two-point (N, 2N) decode timing; "
                         "prompt + 2N must fit --max-seq")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--cache-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"],
                    help="KV-cache storage dtype; bfloat16 halves the "
                         "bytes decode reads per token, int8 quarters "
                         "them (+4 f32 scale bytes per (position, head) "
                         "row — 0.8%% of the f32 cache at head_dim 128)")
    ap.add_argument("--weights-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"],
                    help="serving weights dtype; decode reads every "
                         "weight once per token. int8 = per-channel "
                         "absmax QuantW through the fused GEMV "
                         "(ops/pallas_gemv), quantized once up front")
    ap.add_argument("--paged", action="store_true",
                    help="bench the PAGED cache (serving layout: "
                         "identity block tables over a page pool) "
                         "instead of the contiguous one")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (with --paged)")
    ap.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    args = ap.parse_args()

    claim_device(args.device)  # utils/backend: DeviceError off-chip
    if args.prompt + 2 * args.tokens > args.max_seq:
        print(f"prompt {args.prompt} + 2x{args.tokens} tokens exceeds "
              f"--max-seq {args.max_seq}", file=sys.stderr)
        raise SystemExit(1)

    results = {}
    paged_crcs: list[tuple[int, np.ndarray]] = []
    # Normalize requested kv values to their effective head count (0 means
    # MHA = heads) and dedupe, so e.g. "--kv-heads 0,8" with --heads 8
    # runs once instead of silently overwriting its own results row.
    kvs = list(dict.fromkeys(
        (int(s) or args.heads) for s in args.kv_heads.split(",")
    ))
    for kv in kvs:
        model = TransformerLM(
            vocab=args.vocab, dim=args.dim, heads=args.heads,
            depth=args.depth, max_seq=args.max_seq, kv_heads=kv,
        )
        hkv = model.n_kv
        label = f"kv{hkv}" + ("(MHA)" if hkv == args.heads else "")
        if args.cache_dtype != "float32":
            label += f"+{args.cache_dtype}"
        if args.weights_dtype != "float32":
            label += f"+w{args.weights_dtype}"
        common = dict(
            kv_heads=hkv, cache_dtype=args.cache_dtype,
            weights_dtype=args.weights_dtype,
            model=f"d{args.dim}x{args.depth} h{args.heads} "
                  f"v{args.vocab} b{args.batch} prompt{args.prompt}",
            backend=jax.default_backend(),
            params=count_params(model.init(jax.random.key(0))),
        )
        if args.paged:
            label = "paged/" + label
            per_tok, crc, toks = bench_paged_config(
                model, batch=args.batch, prompt_len=args.prompt,
                gen_tokens=args.tokens, cache_dtype=args.cache_dtype,
                weights_dtype=args.weights_dtype,
                page_size=args.page_size,
            )
            ok = per_tok > 0
            results[label] = {
                "decode_ms_per_tok": round(per_tok * 1e3, 3) if ok
                else None,
                "decode_tokens_per_s": round(args.batch / per_tok) if ok
                else None,
            }
            _emit("paged_decode_tokens_per_s",
                  results[label]["decode_tokens_per_s"], "tokens/s",
                  page_size=args.page_size,
                  decode_ms_per_tok=results[label]["decode_ms_per_tok"],
                  config=label, **common)
            # Per-config CRC row (metric name carries the kv count:
            # `mctpu compare` keeps same-named bench metrics last-wins,
            # so distinct names are what keep a multi-config run fully
            # gateable) + the cross-config accumulator for the combined
            # headline row below.
            _emit(f"paged_greedy_crc_kv{hkv}", int(crc), "crc32",
                  tokens=int(toks.size),
                  batch=args.batch, gen_tokens=args.tokens,
                  page_size=args.page_size, **common)
            paged_crcs.append((hkv, toks))
            continue
        per_tok, prefill_s = bench_decode_config(
            model, batch=args.batch, prompt_len=args.prompt,
            gen_tokens=args.tokens, cache_dtype=args.cache_dtype,
            weights_dtype=args.weights_dtype,
        )
        # cache k+v bytes actually resident per decoded token's attention
        itemsize = jnp.dtype(args.cache_dtype).itemsize
        cache_mb = (
            args.batch * args.max_seq * hkv * model.head_dim * itemsize * 2
            * args.depth / 1e6
        )
        if args.cache_dtype == "int8":
            # + the per-(position, head) f32 absmax scales.
            cache_mb += (
                args.batch * args.max_seq * hkv * 4 * 2 * args.depth / 1e6
            )
        # A non-positive two-point delta means the per-token cost is below
        # the timer's noise floor at these shapes — report null, never a
        # negative throughput.
        ok = per_tok > 0
        results[label] = {
            "decode_ms_per_tok": round(per_tok * 1e3, 3) if ok else None,
            "decode_tokens_per_s": round(args.batch / per_tok) if ok else None,
            "prefill_ms": round(prefill_s * 1e3, 2),
            "cache_mb": round(cache_mb, 1),
        }
        _emit("decode_tokens_per_s",
              results[label]["decode_tokens_per_s"], "tokens/s",
              config=label, **common, **{
                  k: v for k, v in results[label].items()
                  if k != "decode_tokens_per_s"
              })

    if paged_crcs:
        # ONE combined CRC over every config's greedy tokens, in kv
        # order: a divergence in ANY config changes it.
        combined = 0
        total = 0
        for _, toks in sorted(paged_crcs, key=lambda kv_: kv_[0]):
            combined = zlib.crc32(toks.tobytes(), combined)
            total += int(toks.size)
        _emit("paged_greedy_crc", int(combined), "crc32",
              tokens=total,
              configs=len(paged_crcs), batch=args.batch,
              gen_tokens=args.tokens, page_size=args.page_size,
              backend=jax.default_backend())
    best = max(results.items(),
               key=lambda kv_: kv_[1]["decode_tokens_per_s"] or 0)
    # Schema-stamped headline record (obs.schema `bench` event), like
    # bench.py's: `mctpu compare` reads every bench output the same way.
    _emit("decode_best_tokens_per_s", best[1]["decode_tokens_per_s"],
          "tokens/s", config=best[0],
          model=f"d{args.dim}x{args.depth} h{args.heads} v{args.vocab} "
                f"b{args.batch} prompt{args.prompt} cache{args.max_seq}",
          backend=jax.default_backend())


if __name__ == "__main__":
    main()
