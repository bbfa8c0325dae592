"""Open-loop traffic: Poisson arrivals, clipped-lognormal lengths.

The draws are those of `serve/bench.make_workload(len_dist="lognormal")`
(checked once by benchmarks/check_benchmark.py, then independent of the
program's copy): exponential gaps at `rate_rps`, then per request one
lognormal draw for the prompt's length and one for the answer's, each
clipped to its range.

Where the lognormal sits is the cell's to say, from the public trace or
dataset it names (`lengths_from` in the cell's file). A length is given
as {"min", "max"} and two of "median", "mean", "sigma": a lognormal has
two parameters, and a source that publishes a median and a mean fixes
both (sigma^2 = 2 ln(mean / median)). With none of the three it is
make_workload's own shape: median at the geometric middle of the
range, sigma a quarter of the log-range (the tests' cells).

What the two seeds do. The SIZES AND ARRIVALS come from DRAW_SEED, a
constant of this file, and are the same in every run of a cell: the
engine's schedule is a function of them alone, so runs with different
`--seed` do the same work and differ by the machine's noise only (the
benchmark's bounds are set from that; PERF.md has what a fresh draw per
seed costs). `--seed` draws the token ids (and, in run.py, the
weights), so every run serves different text through different weights.

Parameters (the cell's `params`): rate_rps, prompt, out; `n_requests`
only where rate_rps is 0 (everything due at t=0: the capacity run of a
sweep).
"""

from __future__ import annotations

import math

import numpy as np

DRAW_SEED = 24


def mu_sigma(length: dict) -> tuple[float, float]:
    """The lognormal's parameters from two of median, mean, sigma, or
    make_workload's shape over [min, max] from none."""
    lo, hi = int(length["min"]), int(length["max"])
    median, mean, sigma = (length.get(k) for k in ("median", "mean", "sigma"))
    if median is None and mean is None and sigma is None:
        return (0.5 * (np.log(lo) + np.log(hi)),
                (np.log(hi) - np.log(lo)) / 4.0)
    if sigma is None:
        sigma = math.sqrt(2.0 * math.log(mean / median))
    if median is None:
        median = mean * math.exp(-0.5 * sigma * sigma)
    return math.log(median), float(sigma)


def heavy_tail_len(lrng, length: dict) -> int:
    """One lognormal draw clipped to [min, max]."""
    lo, hi = int(length["min"]), int(length["max"])
    if hi <= lo:
        return lo
    mu, sigma = mu_sigma(length)
    v = int(round(float(lrng.lognormal(mu, sigma))))
    return min(max(v, lo), hi)


def draw_sizes(params: dict, *, seconds: float, vocab: int):
    """(arrival_s, prompt_len, out_len) for every request due before
    `seconds`, from DRAW_SEED alone. The generator `rng` is consumed
    exactly as make_workload consumes it (a gap, then the prompt's ids),
    so request i here is request i there."""
    rate = float(params["rate_rps"])
    rng = np.random.default_rng(DRAW_SEED)
    lrng = np.random.default_rng([DRAW_SEED, 3])
    limit = int(params["n_requests"]) if rate <= 0 else None
    t, sizes = 0.0, []
    while limit is None or len(sizes) < limit:
        if rate > 0:
            t += float(rng.exponential(1.0 / rate))
            if t >= seconds:
                break
        plen = heavy_tail_len(lrng, params["prompt"])
        olen = heavy_tail_len(lrng, params["out"])
        rng.integers(0, vocab, (plen,))     # keeps the stream in step
        sizes.append((t, plen, olen))
    return sizes


def generate(params: dict, *, seed: int, seconds: float, vocab: int):
    """[(arrival_s, prompt int32 array, max_new_tokens)], due times
    rising. Token ids are uniform over the vocabulary, from `seed`."""
    ids = np.random.default_rng([int(seed), 0x7A])
    return [
        (t, ids.integers(0, vocab, (plen,)).astype(np.int32), olen)
        for t, plen, olen in draw_sizes(params, seconds=seconds, vocab=vocab)
    ]
