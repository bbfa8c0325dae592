"""What decides `correct`: the tokens the timed window served, held
against the plain reference.

Once the window has closed and the engine's memory is freed, a sample
of the requests that were served tokens (drawn from the seed, the one
with the most served tokens always in it) is run once through the
plain reference of the configuration's family
(`families/<family>/reference.py`, handed in as `forward_logits`):
prompt and served tokens together, teacher-forced. At
every served position the reference has a best logit and a logit for
the token the engine served; their difference is that token's GAP
(0 where the engine served the reference's own first choice). Greedy
serving in a precision the configuration states keeps every gap within
rounding of 0; a token from the wrong page, slot, position or weight
lands logit-sigmas away. Two numbers are held to limits from the
cell's file: `gap_max`, the widest gap, and `gap_mean`, the mean gap
(steadier; it is the one a lower precision moves first). `short`
counts finished requests with another number of tokens than asked,
or a token outside the vocabulary: limit 0.

The control (`lower`) reads, from the same pass, the gap of the token
that the lower-precision reference would have served at each position.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# Sample until this many served tokens are in it, within these counts.
MIN_TOKENS, MIN_REQUESTS, MAX_REQUESTS = 300, 4, 8
ROWS_PAD = 256  # row lists are padded to multiples of this


def pick_sample(served, seed: int):
    """served: [(rid, prompt, out tokens)] with at least one token
    each. Returns the sampled entries: the longest answer first, then
    seeded draws until MIN_TOKENS and MIN_REQUESTS are met."""
    if not served:
        return []
    order = sorted(served, key=lambda s: (-len(s[2]), s[0]))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0xC0])
    rng.shuffle(rest)
    sample, ntok = [order[0]], len(order[0][2])
    for s in rest:
        if len(sample) >= MAX_REQUESTS or (
                ntok >= MIN_TOKENS and len(sample) >= MIN_REQUESTS):
            break
        sample.append(s)
        ntok += len(s[2])
    return sample


def _pad_to(n: int, step: int) -> int:
    return -(-n // step) * step


def gaps(forward_logits, dm: dict, seed: int, sample, max_len: int,
         lower: str | None = None):
    """Per-token gaps of the served tokens (and of the control's picks
    where `lower` is given) for a sample from pick_sample, by the
    family's `forward_logits(dm, seed, seqs, rows, lowers)`. Sequences
    are padded to a third, two thirds or the whole of the deployment's
    `max_len`: three shapes to compile in a checkout, whatever the
    seeds draw. Of `dm` only `max_seq` is read, which every family's
    `dims` gives."""
    step = _pad_to(-(-max_len // 3), 128)
    tmax = min(_pad_to(max(len(p) + len(o) for _, p, o in sample), step),
               dm["max_seq"])
    rmax = _pad_to(max(len(o) for _, _, o in sample), ROWS_PAD)
    seqs, rows = [], []
    for _, prompt, out in sample:
        seq = np.zeros(tmax, np.int32)
        n = len(prompt) + len(out)
        seq[:n] = np.concatenate([prompt, np.asarray(out, np.int32)])
        seqs.append(seq)
        # Row p's logits choose token p+1: the served tokens sit at
        # len(prompt) .. n-1.
        r = np.full(rmax, n - 2, np.int32)
        r[: len(out)] = np.arange(len(prompt) - 1, n - 1)
        rows.append(r)
    lowers = (None, lower) if lower else (None,)
    logits = forward_logits(dm, seed, seqs, rows, lowers)
    served_gaps, lower_gaps = [], []
    for i, (_, _, out) in enumerate(sample):
        n = len(out)
        ref = logits[0][i][:n]
        best = jnp.max(ref, axis=-1)
        tok = jnp.asarray(out, jnp.int32)[:, None]
        served_gaps.append(np.asarray(
            best - jnp.take_along_axis(ref, tok, axis=-1)[:, 0]))
        if lower:
            pick = jnp.argmax(logits[1][i][:n], axis=-1)[:, None]
            lower_gaps.append(np.asarray(
                best - jnp.take_along_axis(ref, pick, axis=-1)[:, 0]))
    out = {"served": np.concatenate(served_gaps)}
    if lower:
        out["lower"] = np.concatenate(lower_gaps)
    return out


def numbers(gap: np.ndarray) -> dict:
    return {"gap_max": float(gap.max()), "gap_mean": float(gap.mean())}


def judge(compared: dict, limits: dict) -> tuple[bool, dict]:
    """compared: name -> number. Every name in `limits` must be there
    and at or under its limit. Returns (correct, {name: {value, limit}})
    in the limits' order, for the result line and stderr."""
    table = {k: {"value": compared.get(k), "limit": limits[k]}
             for k in limits}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
