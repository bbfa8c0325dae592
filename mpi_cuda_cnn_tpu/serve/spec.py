"""Batched speculative decoding policy — the jax-free half (ISSUE 14).

The two proven speculative forms (Leviathan et al.'s model-draft
rejection sampling, Saxena's draft-free prompt lookup — PAPERS.md) lived
only in models/generate.py at B=1, while the serving engine decoded one
token per slot per tick. This module is the policy layer that marries
them to the continuous-batching engine: per-slot k-token PROPOSAL plus
ONE batched verify block per tick, with greedy acceptance committing
anywhere from 1 to k tokens per slot per round.

Division of labor (the scheduler/engine split, applied again):

- THIS module is host-side, numpy-only, and deliberately jax-free
  (`mctpu lint` MCT001): proposal (prompt lookup over the request's own
  committed context), the greedy acceptance law, and the round scaffold
  `run_round` that the serving iteration (core.ServeCore.work) runs
  for the engine and for the fleet's sim storms alike.
- The VERIFY forward is the caller's: engine.PagedEngine.run_spec_tick
  (one jitted paged_forward over every slot's k candidate rows — the
  same token_forward/attend_kv stack every other decode surface shares)
  or fleet.SimCompute.verify (the pure token mix, so the 10^5 storm
  speculates with scheduling real and devices absent).
- Page accounting is scheduler.py's: grow_for_decode(spec_k=) extends
  each decoding slot OPPORTUNISTICALLY toward its speculative width
  (never preempting live work for speculation — a dry pool degrades the
  width toward 1, which is exactly spec-off behavior), and commit_spec
  rolls back pages holding only rejected-draft rows, so a rejected
  token's KV is never live.

The acceptance law here and models/generate._accept_and_emit are the
SAME law in two dialects (numpy host loop vs jitted lax); the no-drift
gate is tests/test_spec_serve.py's randomized equivalence pin between
`accept_len` and the jitted core. At temperature 0 the emitted stream
is the target's own greedy continuation for ANY proposer (the Leviathan
exactness argument) — which is what makes the engine's spec-on outputs
bitwise-equal to spec-off per request, the ISSUE 14 acceptance gate.
T>0 rejection sampling stays a generate.py (B=1) surface: the engine
samples greedily by design, and the distribution-equality tests in
tests/test_spec_sampling.py pin the shared law's sampling form.
"""

from __future__ import annotations

import numpy as np

# The serving spec surface: "off" (one token per slot per tick),
# "lookup" (draft-free prompt lookup — the agentic/template-traffic
# form, and the fleet storms' only form), "draft" (a cheap draft model
# behind the same proposer interface — engine.DraftProposer).
SPEC_MODES = ("off", "lookup", "draft")

_EMPTY = np.empty(0, np.int32)


def empty_spec_fields() -> dict:
    """The zero-valued speculative summary block a spec-off run stamps,
    so every gated metric exists in every run (the fleet/spec-gate
    contract, same as empty_prefix_fields)."""
    return {"spec_rounds": 0, "spec_proposed": 0, "spec_accepted": 0}


def accept_len(u: np.ndarray, y: np.ndarray) -> int:
    """THE greedy speculative acceptance law, host dialect: u holds the
    w verify inputs (u[0] = the slot's current committed token, u[1:]
    the proposals), y the target's per-row greedy picks (y[i] = argmax
    of the logits AFTER input i). Accept the longest prefix where
    proposal u[i+1] equals the target's own pick y[i]; the emitted
    count j = 1 + that prefix (row j-1 is the first-reject replacement
    or the fully-accepted bonus row), exactly
    models/generate._accept_and_emit's j — the randomized equivalence
    test pins the two dialects against drift."""
    w = len(u)
    j = 1
    while j < w and u[j] == y[j - 1]:
        j += 1
    return j


def lookup_propose(ctx: np.ndarray, n_props: int, ngram: int = 2) -> np.ndarray:
    """Draft-free prompt-lookup proposal over the request's committed
    context (prompt + emitted tokens): the n_props tokens that followed
    the MOST RECENT earlier occurrence of the context's current
    ngram-token tail. No earlier occurrence -> repeat the current token
    (acceptance just collapses toward 1, never an error); a match too
    close to the end pads by repeating the last available token. Same
    policy as generate._compiled_lookup_run's propose, in the host
    dialect the serving engine consumes per slot per round — proposals
    move SPEED only, never the emitted law, so the two dialects'
    clamping details are each documented, not mirrored bit-for-bit."""
    if n_props <= 0:
        return _EMPTY
    ctx = np.asarray(ctx, np.int32).reshape(-1)
    n = ctx.size
    cur = ctx[-1]
    if n <= ngram:
        return np.full(n_props, cur, np.int32)
    # Candidate match ends j in [ngram-1, n-2]: the ngram ending at j
    # equals the ngram ending at n-1 (the tail itself is excluded).
    # Pure slice comparisons — this runs once per slot per round in
    # the storm hot loop, so no index arrays are materialized.
    ok = ctx[ngram - 1 : n - 1] == cur
    for d in range(1, ngram):
        ok &= ctx[ngram - 1 - d : n - 1 - d] == ctx[n - 1 - d]
    rev = ok[::-1]
    i = int(np.argmax(rev))       # first True from the END = most recent
    if not rev[i]:
        return np.full(n_props, cur, np.int32)
    j = (ngram - 1) + (ok.size - 1 - i)
    props = ctx[j + 1 : j + 1 + n_props]
    if props.size < n_props:
        pad_tok = props[-1] if props.size else cur
        props = np.concatenate(
            [props, np.full(n_props - props.size, pad_tok, np.int32)]
        )
    return props.astype(np.int32)


class LookupProposer:
    """The draft-free per-slot proposer (Saxena's prompt lookup):
    stateless, host-side, jax-free — the form the fleet's sim storms
    and the engine's default --spec lookup both run."""

    def __init__(self, ngram: int = 2):
        if ngram < 1:
            raise ValueError(f"ngram must be >= 1 (got {ngram})")
        self.ngram = ngram

    def propose(self, ctx: np.ndarray, n_props: int) -> np.ndarray:
        return lookup_propose(ctx, n_props, self.ngram)

    def propose_batch(self, ctxs, n_props):
        """The batched proposer interface run_round drives (the draft
        proposer genuinely batches its device steps; lookup is a pure
        host loop either way)."""
        return [lookup_propose(c, n, self.ngram)
                for c, n in zip(ctxs, n_props)]


def context_tokens(req) -> np.ndarray:
    """The request's committed context (prompt + emitted tokens) as one
    int32 array — the lookup corpus AND the draft window source.

    Cached incrementally on the request (storm hot loop: rebuilding
    prompt+out from scratch every round made the context copy the
    dominant proposal cost): a private growing buffer appends only the
    tokens emitted since the last call, and any shrink of the account
    (a fleet discard re-dispatch clears `out`) rebuilds from scratch.
    Callers treat the returned view as read-only."""
    out = req.out
    n = req.prompt.size + len(out)
    buf = getattr(req, "_spec_ctx", None)
    filled = getattr(req, "_spec_ctx_fill", 0)
    if buf is None or buf.shape[0] < n or filled > n:
        cap = max(2 * n, 64)
        buf = np.empty(cap, np.int32)
        buf[: req.prompt.size] = req.prompt
        filled = req.prompt.size
        req._spec_ctx = buf
    if filled < n:
        buf[filled:n] = out[filled - req.prompt.size :]
    req._spec_ctx_fill = n
    return buf[:n]


def run_round(dslots, widths, proposer, verify):
    """One speculative round over the tick's decoding slots — the
    scaffold core.ServeCore.work runs for every driver:

    1. per slot, propose width-1 draft tokens from its committed
       context and assemble the verify inputs u = [current token,
       proposals] (a width-1 slot verifies just its current token —
       exactly the spec-off tick for that slot);
    2. `verify(rounds)` scores ALL slots' inputs in ONE batched forward
       (rounds: [(slot, u, width)]) and returns each slot's per-row
       greedy picks;
    3. greedy acceptance (`accept_len`) per slot.

    Returns [(slot, width, j, emitted tokens)] — j in [1, width] tokens
    commit; the caller emits, commits cached via
    scheduler.commit_spec (which rolls the rejected-draft pages back),
    and finishes done requests.

    A proposer declaring `needs_slots = True` (the paged draft cache,
    ISSUE 17) carries per-slot KV state: it receives the slot handles
    alongside the contexts, and EVERY slot's real context even at
    n == 0 (a zero-proposal slot still needs its catch-up rows so the
    draft cache tracks the committed stream — stateless proposers keep
    the empty-context fast path).
    """
    need = [w - 1 for w in widths]
    if getattr(proposer, "needs_slots", False):
        ctxs = [context_tokens(s.req) for s in dslots]
        props_list = proposer.propose_batch(ctxs, need, dslots)
    else:
        ctxs = [context_tokens(s.req) if n > 0 else _EMPTY
                for s, n in zip(dslots, need)]
        props_list = proposer.propose_batch(ctxs, need)
    rounds = []
    for s, w, props in zip(dslots, widths, props_list):
        u = np.empty(w, np.int32)
        u[0] = s.req.out[-1]
        u[1:] = props
        rounds.append((s, u, w))
    ys = verify(rounds)
    out = []
    for (s, u, w), y in zip(rounds, ys):
        j = accept_len(u, y)
        out.append((s, w, j, [int(y[i]) for i in range(j)]))
    return out
