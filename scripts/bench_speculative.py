"""Speculative-decoding benchmark: plain greedy vs draft-verified.

Decode at B=1 is latency-bound: every token pays a full sequential
target forward. speculative_generate (models/generate.py) lets a cheap
draft propose k-token chains the target verifies in ONE decode_block
forward — tokens/s scales with the acceptance rate, and the output is
bit-identical to plain greedy by construction (the equality test in
tests/test_generate.py pins it; this bench asserts it again on the real
run).

Acceptance depends on how well the draft predicts the target, so the
bench constructs the honest best case END TO END: both models train on
the cyclic-successor corpus (the deterministic task the test suite's
convergence tests use) until both predict it near-perfectly, then
decode measures plain vs speculative at several k with the REAL
acceptance the trained pair achieves — plus the random-draft worst case
(acceptance ~1/vocab) so both ends of the curve are on record.

One JSON line per row + a summary line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from mpi_cuda_cnn_tpu.models.generate import generate, speculative_generate
from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
from mpi_cuda_cnn_tpu.obs.schema import make_record
from mpi_cuda_cnn_tpu.train.lm import make_lm_state, make_lm_train_step
from mpi_cuda_cnn_tpu.train.optimizer import make_optimizer
from mpi_cuda_cnn_tpu.utils.backend import claim_device
from mpi_cuda_cnn_tpu.utils.sync import two_point

_T0 = time.perf_counter()


def train_on_cycle(model, *, steps, batch, seq, lr=3e-3, seed=0):
    """Fit `model` to token[t+1] = token[t] + 1 (mod vocab)."""
    opt = make_optimizer(lr, opt="adamw", schedule="constant")
    step_fn = make_lm_train_step(model, opt, attn_impl="oracle",
                                 seq_len=seq)
    state = make_lm_state(model, opt, seed)
    rng = np.random.default_rng(seed)
    loss = float("nan")
    for _ in range(steps):
        starts = rng.integers(0, model.vocab, size=(batch, 1))
        w = (starts + np.arange(seq + 1)[None, :]) % model.vocab
        toks = jnp.asarray(w, jnp.int32)
        state, m = step_fn(state, toks[:, :-1], toks[:, 1:])
        loss = m["loss"]
    return state["params"], float(loss)


def train_on_text(model, tokens, *, steps, batch, seq, lr=1e-3, seed=0):
    """Fit `model` to a real token stream (random windows, the
    LMTrainer._sample_batch scheme) — for the self-corpus lookup row."""
    opt = make_optimizer(lr, opt="adamw", schedule="constant")
    step_fn = make_lm_train_step(model, opt, attn_impl="oracle",
                                 seq_len=seq)
    state = make_lm_state(model, opt, seed)
    rng = np.random.default_rng(seed)
    n = len(tokens) - seq
    loss = float("nan")
    for _ in range(steps):
        starts = rng.integers(0, n, size=batch)
        idx = starts[:, None] + np.arange(seq + 1)[None, :]
        w = jnp.asarray(tokens[idx], jnp.int32)
        state, m = step_fn(state, w[:, :-1], w[:, 1:])
        loss = m["loss"]
    return state["params"], float(loss)


def timed_tokens(fn, n, attempts=3, floor=0.0):
    """(s/token, suspect) of a generate-style call via the shared
    two-point core: fn(m) must produce m tokens and force completion.
    A backend transient can push even the median-of-3 slope NEGATIVE
    (observed: a banked -0.095 ms/tok row) or impossibly FAST (observed
    2026-07-31: a lookup-k8 slope reading 85x speedup, ~7x above every
    other run's measurement) — a value at or below `floor` is
    re-measured up to `attempts` times. Callers pass plain/(k*4) for
    speculative modes (per-round emit <= k tokens; banked legitimate
    rows reach ~2x k because the verify block + while_loop amortize far
    better than one plain step per round, and a first 3x-k margin was
    itself outrun by a legitimate run). If every attempt stays at or
    below the floor the LAST positive sample is returned with
    suspect=True — the row is emitted flagged, never silently dropped
    and never allowed to kill the remaining bench rows (a raise here
    cost one banked capture its speculative section; non-positive
    slopes with no positive sample at all still raise)."""

    def run(m):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(m))
        return time.perf_counter() - t0

    run(n), run(2 * n)  # warm both program sizes
    last_positive = None
    for _ in range(attempts):
        t = two_point(run, n, warmup=0)
        if t > floor:
            return t, False
        if t > 0:
            last_positive = t
    if last_positive is not None:
        return last_positive, True
    raise RuntimeError(
        f"two-point slope stayed non-positive over {attempts} "
        "median-of-3 attempts — backend too unstable to measure"
    )


def try_timed(fn, n, floor):
    """timed_tokens for the SPECULATIVE rows: an unmeasurable mode
    (persistently non-positive slope) returns (None, True) so the
    caller emits a skipped row and the bench CONTINUES — one jittery
    mode must not cost the capture every later row (it did once:
    banked bench_speculative_final_r5 rc=1). The plain baselines keep
    the raise — without them the speedup columns mean nothing."""
    try:
        return timed_tokens(fn, n, floor=floor)
    except RuntimeError:
        return None, True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--draft-dim", type=int, default=128)
    ap.add_argument("--draft-depth", type=int, default=1)
    ap.add_argument("--vocab", type=int, default=251)
    ap.add_argument("--max-seq", type=int, default=2048)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=256)
    ap.add_argument("--ks", default="2,4,8")
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--self-corpus-steps", type=int, default=300,
                    help="train a fresh target on the framework's own "
                         "sources and measure lookup speculation on real "
                         "code — the technique's claimed use case; 0 "
                         "disables the row")
    ap.add_argument("--device", default="auto", choices=["auto", "tpu", "cpu"])
    args = ap.parse_args()

    claim_device(args.device)  # utils/backend: DeviceError off-chip

    target = TransformerLM(vocab=args.vocab, dim=args.dim,
                           heads=args.heads, depth=args.depth,
                           max_seq=args.max_seq)
    draft = TransformerLM(vocab=args.vocab, dim=args.draft_dim,
                          heads=2, depth=args.draft_depth,
                          max_seq=args.max_seq)
    t_params, t_loss = train_on_cycle(
        target, steps=args.train_steps, batch=8, seq=128
    )
    d_params, d_loss = train_on_cycle(
        draft, steps=4 * args.train_steps, batch=8, seq=128
    )
    prompt = jnp.asarray(
        (np.arange(args.prompt)[None, :] % args.vocab), jnp.int32
    )

    t_plain, _ = timed_tokens(
        lambda m: generate(target, t_params, prompt, m), args.tokens
    )
    want = np.asarray(generate(target, t_params, prompt, args.tokens))
    rows = [{
        "bench": "speculative", "mode": "plain_greedy",
        "ms_per_tok": round(t_plain * 1e3, 3),
        "tokens_per_s": round(1.0 / t_plain),
        "target_loss": round(t_loss, 4), "draft_loss": round(d_loss, 4),
    }]
    print(json.dumps(rows[0]), flush=True)

    best = (rows[0]["tokens_per_s"], "plain")
    for k in (int(x) for x in args.ks.split(",")):
        got, stats = speculative_generate(
            target, t_params, draft, d_params, prompt, args.tokens,
            k=k, return_stats=True,
        )
        exact = bool(np.array_equal(np.asarray(got), want))
        t_spec, sus = try_timed(
            lambda m, k=k: speculative_generate(
                target, t_params, draft, d_params, prompt, m, k=k
            ),
            args.tokens, t_plain / (k * 4.0),
        )
        if t_spec is None:
            print(json.dumps({"bench": "speculative",
                              "mode": f"draft_k{k}",
                              "skipped": "unmeasurable"}), flush=True)
            continue
        row = {
            "bench": "speculative", "mode": f"draft_k{k}",
            "ms_per_tok": round(t_spec * 1e3, 3),
            "tokens_per_s": round(1.0 / t_spec),
            "mean_accepted": round(stats["mean_accepted"], 2),
            "speedup_vs_plain": round(t_plain / t_spec, 2),
            "greedy_exact": exact,
            **({"suspect_fast": True} if sus else {}),
        }
        print(json.dumps(row), flush=True)
        rows.append(row)
        if row["tokens_per_s"] > best[0] and exact and not sus:
            best = (row["tokens_per_s"], f"k={k}")

    # Draft-FREE prompt-lookup speculation (the CLI-reachable form):
    # needs the continuation's n-grams to have earlier occurrences, so
    # its prompt spans > one full cycle of the corpus.
    from mpi_cuda_cnn_tpu.models.generate import lookup_speculative_generate

    lk_prompt = jnp.asarray(
        (np.arange(args.vocab + 49)[None, :] % args.vocab), jnp.int32
    )
    lk_want = np.asarray(generate(target, t_params, lk_prompt, args.tokens))
    lk_plain, _ = timed_tokens(
        lambda m: generate(target, t_params, lk_prompt, m), args.tokens
    )
    for k in (int(x) for x in args.ks.split(",")):
        lk_toks, lstats = lookup_speculative_generate(
            target, t_params, lk_prompt, args.tokens, k=k,
            return_stats=True,
        )
        lk_got = np.asarray(lk_toks)
        t_lk, sus = try_timed(
            lambda m, k=k: lookup_speculative_generate(
                target, t_params, lk_prompt, m, k=k
            ),
            args.tokens, lk_plain / (k * 4.0),
        )
        if t_lk is None:
            print(json.dumps({"bench": "speculative",
                              "mode": f"lookup_k{k}",
                              "skipped": "unmeasurable"}), flush=True)
            continue
        row = {
            "bench": "speculative", "mode": f"lookup_k{k}",
            "ms_per_tok": round(t_lk * 1e3, 3),
            "tokens_per_s": round(1.0 / t_lk),
            "mean_accepted": round(lstats["mean_accepted"], 2),
            "speedup_vs_plain": round(lk_plain / t_lk, 2),
            "greedy_exact": bool(np.array_equal(lk_got, lk_want)),
            **({"suspect_fast": True} if sus else {}),
        }
        print(json.dumps(row), flush=True)
        if row["tokens_per_s"] > best[0] and row["greedy_exact"] \
                and not sus:
            best = (row["tokens_per_s"], f"lookup_k{k}")

    # Rejection-sampling speculation at temperature 0.8 (round 5): the
    # same trained pair, now SAMPLING — acceptance is min(1, p/q) per
    # proposal instead of argmax matching, output law == plain
    # temperature sampling's (tests/test_spec_sampling.py pins the
    # distribution equality; no bitwise assert is possible for sampling).
    temp = 0.8
    skey = jax.random.key(11)
    t_plain_T, _ = timed_tokens(
        lambda m: generate(target, t_params, prompt, m, temperature=temp,
                           key=skey),
        args.tokens,
    )
    print(json.dumps({
        "bench": "speculative", "mode": f"plain_sample_T{temp}",
        "ms_per_tok": round(t_plain_T * 1e3, 3),
        "tokens_per_s": round(1.0 / t_plain_T),
    }), flush=True)
    for k in (int(x) for x in args.ks.split(",")):
        _, sst = speculative_generate(
            target, t_params, draft, d_params, prompt, args.tokens,
            k=k, temperature=temp, key=skey, return_stats=True,
        )
        t_sT, susT = try_timed(
            lambda m, k=k: speculative_generate(
                target, t_params, draft, d_params, prompt, m, k=k,
                temperature=temp, key=skey,
            ),
            args.tokens, t_plain_T / (k * 4.0),
        )
        if t_sT is None:
            print(json.dumps({"bench": "speculative",
                              "mode": f"draft_k{k}_T{temp}",
                              "skipped": "unmeasurable"}), flush=True)
            continue
        print(json.dumps({
            "bench": "speculative", "mode": f"draft_k{k}_T{temp}",
            "ms_per_tok": round(t_sT * 1e3, 3),
            "tokens_per_s": round(1.0 / t_sT),
            "mean_accepted": round(sst["mean_accepted"], 2),
            "speedup_vs_plain": round(t_plain_T / t_sT, 2),
            **({"suspect_fast": True} if susT else {}),
        }), flush=True)
    # Lookup sampling on the cycle-spanning prompt.
    lk_plain_T, _ = timed_tokens(
        lambda m: generate(target, t_params, lk_prompt, m,
                           temperature=temp, key=skey),
        args.tokens,
    )
    for k in (int(x) for x in args.ks.split(",")):
        _, lst = lookup_speculative_generate(
            target, t_params, lk_prompt, args.tokens, k=k,
            temperature=temp, key=skey, return_stats=True,
        )
        t_lkT, susLT = try_timed(
            lambda m, k=k: lookup_speculative_generate(
                target, t_params, lk_prompt, m, k=k, temperature=temp,
                key=skey,
            ),
            args.tokens, lk_plain_T / (k * 4.0),
        )
        if t_lkT is None:
            print(json.dumps({"bench": "speculative",
                              "mode": f"lookup_k{k}_T{temp}",
                              "skipped": "unmeasurable"}), flush=True)
            continue
        print(json.dumps({
            "bench": "speculative", "mode": f"lookup_k{k}_T{temp}",
            "ms_per_tok": round(t_lkT * 1e3, 3),
            "tokens_per_s": round(1.0 / t_lkT),
            "mean_accepted": round(lst["mean_accepted"], 2),
            "speedup_vs_plain": round(lk_plain_T / t_lkT, 2),
            **({"suspect_fast": True} if susLT else {}),
        }), flush=True)

    # Lookup on REAL text: a fresh target trained briefly on the
    # framework's own sources (char-level — `--corpus self`), prompt =
    # the corpus head. Acceptance here is the honest answer to "does
    # prompt-lookup help on code?", not a cyclic-toy upper bound.
    if args.self_corpus_steps:
        from mpi_cuda_cnn_tpu.train.lm_trainer import load_corpus

        text = load_corpus("self")
        st = TransformerLM(vocab=256, dim=args.dim, heads=args.heads,
                           depth=args.depth, max_seq=args.max_seq)
        st_params, st_loss = train_on_text(
            st, text, steps=args.self_corpus_steps, batch=8, seq=256
        )
        sp = jnp.asarray(np.asarray(text[:512])[None, :], jnp.int32)
        sp_want = np.asarray(generate(st, st_params, sp, args.tokens))
        t_sp_plain, _ = timed_tokens(
            lambda m: generate(st, st_params, sp, m), args.tokens
        )
        got, sstats = lookup_speculative_generate(
            st, st_params, sp, args.tokens, k=8, return_stats=True
        )
        t_sp_lk, sus_sp = try_timed(
            lambda m: lookup_speculative_generate(st, st_params, sp, m,
                                                  k=8),
            args.tokens, t_sp_plain / (8 * 4.0),
        )
        if t_sp_lk is None:
            print(json.dumps({"bench": "speculative",
                              "mode": "self_corpus_lookup_k8",
                              "skipped": "unmeasurable"}), flush=True)
            t_sp_lk = None
        if t_sp_lk is not None:
            print(json.dumps({
                "bench": "speculative", "mode": "self_corpus_lookup_k8",
                "train_steps": args.self_corpus_steps,
                "train_loss": round(st_loss, 3),
                "plain_ms_per_tok": round(t_sp_plain * 1e3, 3),
                "ms_per_tok": round(t_sp_lk * 1e3, 3),
                "mean_accepted": round(sstats["mean_accepted"], 2),
                "speedup_vs_plain": round(t_sp_plain / t_sp_lk, 2),
                "greedy_exact": bool(
                    np.array_equal(np.asarray(got), sp_want)
                ),
                **({"suspect_fast": True} if sus_sp else {}),
            }), flush=True)

    # Worst case on record: an untrained draft accepts ~1/vocab.
    rand = draft.init(jax.random.key(99))
    _, rstats = speculative_generate(
        target, t_params, draft, rand, prompt, args.tokens, k=4,
        return_stats=True,
    )
    t_rand, sus_r = try_timed(
        lambda m: speculative_generate(
            target, t_params, draft, rand, prompt, m, k=4
        ),
        args.tokens, t_plain / (4 * 4.0),
    )
    if t_rand is None:
        print(json.dumps({"bench": "speculative",
                          "mode": "random_draft_k4",
                          "skipped": "unmeasurable"}), flush=True)
    else:
        print(json.dumps({
            "bench": "speculative", "mode": "random_draft_k4",
            "ms_per_tok": round(t_rand * 1e3, 3),
            "mean_accepted": round(rstats["mean_accepted"], 2),
            "speedup_vs_plain": round(t_plain / t_rand, 2),
            **({"suspect_fast": True} if sus_r else {}),
        }), flush=True)

    # Schema-stamped headline record (obs.schema `bench` event), like
    # bench.py's: `mctpu compare` reads every bench output the same way.
    print(json.dumps(make_record(
        "bench", time.perf_counter() - _T0,
        metric="speculative_decode_tokens_per_s",
        value=best[0], unit="tokens/s", config=best[1],
        plain_tokens_per_s=rows[0]["tokens_per_s"],
        model=f"d{args.dim}x{args.depth} draft d{args.draft_dim}x"
              f"{args.draft_depth} v{args.vocab} B=1",
        backend=jax.default_backend(),
    )))


if __name__ == "__main__":
    main()
