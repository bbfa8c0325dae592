"""Expert parallelism (MoE) over an 'expert' mesh axis.

The reference has no MoE/routing of any kind (SURVEY.md §2 parallelism
checklist: "EP: absent") — like sequence parallelism, this is a
first-class capability of the framework rather than a parity item, and it
completes the parallelism family: DP (dp.py), TP (tp.py), PP (pp.py),
SP (sp.py), EP (here).

Design — Switch-style top-1 routing with static shapes (XLA needs them):

- Gating: per-token softmax over experts, top-1 expert, gate = its prob.
- Capacity: each expert accepts at most C tokens per device shard
  (C = ceil(T/E * capacity_factor)); overflow tokens are DROPPED (their
  MoE output is 0, the residual connection carries them — standard
  Switch behavior) via position-in-expert cumsum masking.
- Dispatch/combine are dense one-hot tensors (T, E, C) contracted with
  einsum — the MXU-friendly formulation (no scatter/gather).
- EP: experts shard over the 'expert' axis; a tiled all_to_all turns the
  per-device (E, C, D) dispatch buffer into (E/P, P*C, D) — each device
  holds ALL tokens routed to ITS experts — the experts run as one batched
  einsum, and the inverse all_to_all returns outputs to the tokens'
  owners. Two collectives per layer, exactly like the reference
  frameworks this pattern comes from, riding ICI here.

`moe_mlp` is the SPMD body (callable inside shard_map, or standalone with
axis=None for the single-device oracle the tests compare against).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..obs.trace import annotate
from ..ops.pallas_expert_mlp import expert_mlp, fits as expert_mlp_fits
from ..ops.pallas_gemv import swiglu
from ..utils.donation import donate_jit

EXPERT_AXIS = "expert"


def init_moe_params(key, dim: int, hidden: int, n_experts: int) -> dict:
    """Gate + expert-stacked MLP weights. Experts are stacked on a leading
    dim so they shard/slice cleanly: w1 (E, D, H), w2 (E, H, D)."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / jnp.sqrt(jnp.asarray(dim, jnp.float32))
    scale_hid = 1.0 / jnp.sqrt(jnp.asarray(hidden, jnp.float32))
    return {
        "gate": jax.random.normal(k1, (dim, n_experts), jnp.float32) * scale_in,
        "w1": jax.random.normal(k2, (n_experts, dim, hidden), jnp.float32) * scale_in,
        "w2": jax.random.normal(k3, (n_experts, hidden, dim), jnp.float32) * scale_hid,
    }


def router_dispatch(x, gate_w, n_experts: int, capacity: int, k: int = 1,
                    dtype=None, return_stats: bool = False):
    """THE routing core — top-k choice + capacity slot assignment, fused.

    Builds the ONE (T, E, C) dispatch tensor the MoE einsums consume,
    DIRECTLY in `dtype` (default x.dtype): the (T, E, C) writes are the
    dominant routing cost (2.7 GB/layer at the profiled T=16k config,
    PERF.md "MoE single-chip attribution"), and the old f32-build +
    cast + separate combine tensor paid that cost four ways — f32 build,
    cast read+write, second (combine) build per choice, second cast.
    The gate weighting now travels as a (T, E) map instead of a second
    (T, E, C) tensor: each token's chosen experts are DISTINCT (lax.top_k),
    so at most one choice lands on any (t, e) pair and
    combine == dispatch * gate_te[:, :, None] exactly.

    All queue math (cumsum positions, capacity masks) stays f32 — exact
    small-integer arithmetic, which bf16 loses past 256 tokens; only the
    (T, E, C) outer products take `dtype`.

    k=1 is Switch routing (raw top prob as the gate); k>1 renormalizes
    over the chosen k (GShard). Capacity is allocated by CHOICE
    PRIORITY: all tokens' 1st choices claim slots before any 2nd choice
    does, so adding k > 1 never evicts a would-be top-1 assignment. Per
    choice, slots go in token order.

    Returns (dispatch, gate_te, aux_loss):
      dispatch: (T, E, C) in {0, 1}, `dtype` — token t occupies slot c
                of expert e;
      gate_te:  (T, E) f32 — the token's (renormalized) gate for each
                chosen-and-kept expert, 0 elsewhere;
      aux_loss: scalar f32 load-balancing loss (Switch form over FIRST
                choices: the signal that spreads primary assignments).

    return_stats=True swaps aux_loss for its PER-EXPERT SUFFICIENT
    STATISTICS (first_choice_count (E,), prob_sum (E,)) — additive
    across token chunks, so moe_mlp's chunked scan can accumulate them
    in the carry and form the balance loss ONCE GLOBALLY (a mean of
    per-chunk losses is a different, biased objective: the product of
    per-chunk means is not the mean of the product).
    """
    t = x.shape[0]
    dtype = jnp.dtype(dtype) if dtype is not None else x.dtype
    logits = x @ gate_w                                   # (T, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, k)                   # (T, k), distinct
    gates = vals if k == 1 else vals / jnp.sum(vals, axis=-1, keepdims=True)

    dispatch = jnp.zeros((t, n_experts, capacity), dtype)
    gate_te = jnp.zeros((t, n_experts), jnp.float32)
    used = jnp.zeros((n_experts,), jnp.float32)  # kept slots per expert
    # Python loop over choices: unrolled at trace time, so the compiled
    # program grows linearly in k. Fine for the MoE regimes this routing
    # targets (k is 1 or 2 in every shipped config; even 4 is cheap).
    for j in range(k):
        onehot = jax.nn.one_hot(idx[:, j], n_experts, dtype=jnp.float32)
        pos = (jnp.cumsum(onehot, axis=0) - 1.0 + used[None, :]) * onehot
        keep = (pos < capacity).astype(jnp.float32) * onehot
        slot = jax.nn.one_hot(
            jnp.sum(pos * onehot, axis=-1).astype(jnp.int32), capacity,
            dtype=dtype,
        )
        dispatch = dispatch + keep.astype(dtype)[:, :, None] * slot[:, None, :]
        gate_te = gate_te + keep * gates[:, j, None]
        used = used + jnp.sum(keep, axis=0)
    onehot1 = jax.nn.one_hot(idx[:, 0], n_experts, dtype=jnp.float32)
    if return_stats:
        return dispatch, gate_te, (jnp.sum(onehot1, axis=0),
                                   jnp.sum(probs, axis=0))
    aux_loss = jnp.sum(
        jnp.mean(onehot1, axis=0) * jnp.mean(probs, axis=0)
    ) * n_experts
    return dispatch, gate_te, aux_loss


def top1_dispatch(x, gate_w, n_experts: int, capacity: int):
    """Switch top-1 routing for tokens x: (T, D) — the dense-tensor view
    of router_dispatch (kept for callers/tests that want the classic
    (dispatch, combine) pair; the hot path consumes router_dispatch's
    fused form and never builds `combine`).

    Returns (dispatch, combine, aux_loss):
      dispatch: (T, E, C) f32 in {0, 1} — token t occupies slot c of
                expert e (at most one nonzero per token);
      combine:  (T, E, C) f32 — dispatch weighted by the token's gate;
      aux_loss: scalar load-balancing loss (mean_prob · mean_assignment
                · E, the Switch auxiliary), to be added by the caller.
    """
    return topk_dispatch(x, gate_w, n_experts, capacity, k=1)


def topk_dispatch(x, gate_w, n_experts: int, capacity: int, k: int = 2):
    """Top-k routing (GShard-style) for tokens x: (T, D) — dense-tensor
    view of router_dispatch; see top1_dispatch. k=1 reproduces
    top1_dispatch exactly (tested)."""
    dispatch, gate_te, aux = router_dispatch(
        x, gate_w, n_experts, capacity, k=k, dtype=jnp.float32
    )
    # combine == dispatch * gate_te exactly: the chosen experts per token
    # are distinct, so each (t, e) pair carries at most one choice's gate.
    combine = dispatch * gate_te[:, :, None]
    return dispatch, combine, aux


def _expert_ffn(h, w1, w2):
    """Batched expert MLP: h (E_local, S, D) x w1 (E_local, D, H) ..."""
    return jnp.einsum("esh,ehd->esd", jax.nn.relu(jnp.einsum("esd,edh->esh", h, w1)), w2)


def moe_mlp(
    x,
    params: dict,
    *,
    n_experts: int,
    capacity_factor: float = 1.25,
    axis: str | None = EXPERT_AXIS,
    top_k: int = 1,
    dispatch_chunk: int = 0,
    dispatch_dtype=None,
    _aux_stats: bool = False,
):
    """MoE MLP for x: (T, D) local tokens. SPMD body when `axis` names a
    mesh axis — then params["w1"]/["w2"] hold only THIS device's E/P
    expert stack (sharded on their leading dim; the gate is replicated) —
    or the exact single-device dense oracle when axis=None (full stacks).
    top_k=1 is Switch routing; top_k=2 the GShard form (capacity scales
    with k so per-expert slots track the k*T total assignments).
    Returns (y: (T, D), aux_loss: scalar).

    dispatch_chunk > 0 routes tokens in fixed-size chunks (a lax.scan
    sharing the expert weights) — the single-chip MoE throughput lever.
    The dense (T, E, C) dispatch/combine einsums cost 2*E*C*T*D with
    C = ceil(T*k*cf/E), i.e. ~2*k*cf*T^2*D — QUADRATIC in local tokens;
    at T = 16384 that term dwarfs the expert FFN's useful FLOPs 8x
    (scripts/profile_moe.py banks the attribution). Chunking makes it
    linear in T while staying pure MXU einsums — the router + dispatch
    build runs INSIDE the scan body, so the (chunk, E, C) tensor is
    built, consumed, and freed per iteration and nothing routing-sized
    ever exists at batch extent. Capacity becomes per-chunk
    (ceil(chunk*k*cf/E) slots per expert per chunk) — the same
    estimator change every microbatched MoE trainer accepts, and
    bitwise-identical to unchunked when nothing drops (tested). The aux
    loss is formed ONCE GLOBALLY from per-expert count/prob sums
    accumulated in the scan carry — NOT a mean of per-chunk losses
    (that was a biased estimator: the product of per-chunk means is not
    the mean of the product, so toggling dispatch_chunk used to change
    the training objective; round-5 advisor finding). Chunked and
    unchunked aux now agree to float rounding (tested near-exact).
    Under EP (`axis` set) chunking is rejected:
    each shard already routes only its T/P local tokens, which is the
    same quadratic-term reduction the mesh provides for free.

    dispatch_dtype overrides the dispatch tensor's dtype (default:
    x.dtype — bf16 under a bf16 compute path). jnp.bfloat16 under an
    f32 path halves the routing-tensor build/read bytes at a bounded
    cost: dispatch entries are exact {0, 1} in any float dtype, so only
    the einsum accumulation dtype changes.

    _aux_stats is the chunked scan's internal hook: the second return
    becomes router_dispatch's additive per-expert (count, prob-sum)
    stats instead of the scalar loss."""
    t, d = x.shape
    if dispatch_chunk and dispatch_chunk < t:
        if axis is not None:
            raise ValueError(
                "dispatch_chunk is the SINGLE-DEVICE quadratic-dispatch "
                f"lever; under EP (axis={axis!r}) the mesh already "
                "shards the routed tokens — drop one of the two"
            )
        if t % dispatch_chunk:
            raise ValueError(
                f"tokens {t} not divisible by dispatch_chunk "
                f"{dispatch_chunk}"
            )

        def chunk_body(carry, xc):
            count_sum, prob_sum = carry
            yc, (f, p) = moe_mlp(
                xc, params, n_experts=n_experts,
                capacity_factor=capacity_factor, axis=None, top_k=top_k,
                dispatch_dtype=dispatch_dtype, _aux_stats=True,
            )
            return (count_sum + f, prob_sum + p), yc

        xs = x.reshape(t // dispatch_chunk, dispatch_chunk, d)
        zero = jnp.zeros((n_experts,), jnp.float32)
        (count_sum, prob_sum), ys = lax.scan(chunk_body, (zero, zero), xs)
        # The GLOBAL Switch balance loss from the accumulated sufficient
        # statistics: identical objective to unchunked routing (only the
        # summation order differs — near-exact, tested).
        aux = jnp.sum(
            (count_sum / t) * (prob_sum / t)
        ) * n_experts
        return ys.reshape(t, d), aux
    capacity = max(1, -int(-t * top_k * capacity_factor // n_experts))  # ceil
    # Fused router (router_dispatch): ONE (T, E, C) tensor built directly
    # in the einsum dtype + a (T, E) gate map — never an f32 build/cast
    # round-trip, never a second (T, E, C) combine tensor.
    with annotate("ep.router_build"):
        dispatch, gate_te, aux = router_dispatch(
            x, params["gate"], n_experts, capacity, k=top_k,
            dtype=dispatch_dtype or x.dtype, return_stats=_aux_stats,
        )
    with annotate("ep.dispatch_einsum"):
        expert_in = jnp.einsum("tec,td->ecd", dispatch, x)    # (E, C, D)

    if axis is None:
        with annotate("ep.expert_ffn"):
            expert_out = _expert_ffn(expert_in, params["w1"], params["w2"])
    else:
        p = lax.axis_size(axis)
        if n_experts % p:
            raise ValueError(f"experts {n_experts} not divisible by axis size {p}")
        e_local = n_experts // p
        if params["w1"].shape[0] == e_local:
            # Pre-sharded stacks (moe_param_specs): O(E/P) param memory —
            # the standalone EP layer's layout.
            w1, w2 = params["w1"], params["w2"]
        elif params["w1"].shape[0] == n_experts:
            # Replicated full stacks, sliced to this device's experts by
            # axis index — the layout a replicated-params train step
            # (e.g. the SP LM step) provides. Compute/token routing is
            # still expert-parallel; only param memory is not scaled.
            # Gradient note: the dynamic_slice transpose scatters each
            # expert's cotangent into its rows on exactly one device, so
            # a pmean over the axis yields the same (1/P)-scaled gradient
            # as every replicated leaf.
            me = lax.axis_index(axis)
            w1 = lax.dynamic_slice_in_dim(params["w1"], me * e_local, e_local, 0)
            w2 = lax.dynamic_slice_in_dim(params["w2"], me * e_local, e_local, 0)
        else:
            raise ValueError(
                f"w1 holds {params['w1'].shape[0]} experts; expected "
                f"{e_local} (sharded over {axis!r}) or {n_experts} "
                "(replicated)"
            )
        # (E, C, D) -> (E/P, P*C, D): every device receives the slots
        # destined for ITS experts from every device.
        with annotate("ep.all_to_all_dispatch"):
            expert_in = lax.all_to_all(
                expert_in, axis, split_axis=0, concat_axis=1, tiled=True
            )
        with annotate("ep.expert_ffn"):
            expert_out = _expert_ffn(expert_in, w1, w2)
        # Inverse: (E/P, P*C, D) -> (E, C, D), back on the tokens' owner.
        with annotate("ep.all_to_all_combine"):
            expert_out = lax.all_to_all(
                expert_out, axis, split_axis=1, concat_axis=0, tiled=True
            )

    with annotate("ep.combine_einsum"):
        if top_k == 1:
            # Switch routing: each token occupies at most ONE (e, c)
            # slot, so the gate is a per-token SCALAR — contract the
            # SAME dispatch tensor the forward path already built and
            # scale the (T, D) result. No (T, E, C) combine tensor
            # exists at all: the routing-tensor traffic drops from
            # 2 writes + 2 reads to 1 write + 2 reads. Exact: the one
            # nonzero product per row makes the reassociation bitwise.
            gate_t = jnp.sum(gate_te, axis=-1)            # (T,)
            y = jnp.einsum("tec,ecd->td", dispatch, expert_out)
            y = y * gate_t.astype(y.dtype)[:, None]
        else:
            # Top-k: the combine weights are ONE broadcast multiply of
            # the dispatch tensor by the (T, E) gate map — never a
            # second routed build (the old form assembled combine from
            # k more one-hot products in f32 and cast it). Exact:
            # dispatch entries are {0, 1} and each (t, e) pair carries
            # at most one choice's gate.
            combine = dispatch * gate_te.astype(dispatch.dtype)[:, :, None]
            y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y.astype(x.dtype), aux


def moe_param_specs(axis: str = EXPERT_AXIS) -> dict:
    """PartitionSpecs for init_moe_params' pytree: expert stacks sharded
    on their leading (expert) dim — per-device memory O(E/P), the point
    of EP — gate replicated (every device routes its own tokens)."""
    return {"gate": P(), "w1": P(axis), "w2": P(axis)}


def moe_mlp_inference(x, params: dict, *, n_experts: int, top_k: int = 1):
    """No-drop top-k MoE for INFERENCE: every token runs through every
    expert and the router's choice(s) select (and weight) the output.

    E-fold MLP FLOPs, but O(T*E*H) memory instead of the dispatch
    formulation's O(T^2) no-drop tensors — and, unlike capacity routing,
    token t's output depends on token t alone (no batch contamination, no
    causality leak through queue positions). The right trade for decode
    and prefill; training keeps the capacity-dropped dispatch (moe_mlp).
    top_k > 1 mirrors topk_dispatch's renormalized combined gates.
    """
    probs = jax.nn.softmax((x @ params["gate"]).astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)                   # (T, k)
    gates = (
        vals if top_k == 1
        else vals / jnp.sum(vals, axis=-1, keepdims=True)
    )  # same gate rule as topk_dispatch
    h = jax.nn.relu(jnp.einsum("td,edh->teh", x, params["w1"]))
    y_all = jnp.einsum("teh,ehd->ted", h, params["w2"])       # (T, E, D)
    weight = jnp.zeros_like(probs)
    weight = jnp.put_along_axis(
        weight, idx, gates, axis=-1, inplace=False
    )                                                          # (T, E)
    y = jnp.einsum("ted,te->td", y_all, weight.astype(y_all.dtype))
    return y.astype(x.dtype)


# Sorted (token, expert) pairs a step of moe_held_inference's WALK, the
# form of the grouped products where few pairs land on this chip:
# dots.vlm1's decode tick of 64 rows sends about 30 pairs to its 16 of
# 256 experts, its 32-row chunk about 16, and XLA's grouped kernel pays
# for every row of its tile in every group it touches (PERF.md section
# 6, PR 28).
_PAIR_CHUNK = 128


def pairs_landing(rows: int, spec) -> int:
    """The (token, expert) pairs of a forward of `rows` tokens that can
    land on this chip's experts if the router spreads them evenly:
    rows x top_k x held / experts. A shape, known when the program is
    traced: 3,072 for smallthinker's 512-row chunk (64 of 64 held,
    top-6), 192 for its tick of 32; 32 and 16 for dots.vlm1's tick and
    chunk."""
    return rows * spec.top_k * len(spec.held) // spec.experts


def tiled_products(rows: int, spec, bank: dict) -> bool:
    """Which form moe_held_inference's grouped products take, from
    shapes alone. True: ops/pallas_expert_mlp's kernel over the (row
    tile, expert) pairs that meet, where more pairs can land here than
    ONE step of the walk holds — the walk would then stream an expert's
    matrices again in every step its rows straddle and start three
    grouped calls a step, 72 a layer at 3,072 pairs — and an expert's
    matrices fit the kernel's VMEM twice over. Else the walk.
    Placed on the v5e at smallthinker's shapes, 3 x (2560, 768) bf16 an
    expert (PERF.md section 6, PR 33; ms a layer, walk / kernel): 3,072
    pairs over 64 experts 2.48 / 1.25; 600 over 64 (a short last
    chunk) 1.83 / 1.06; 192 over 60 (a full tick) 1.55 / 0.96; 90 over
    48 1.23 / 0.77. Nothing was measured where one step holds all that
    can land (dots.vlm1's 32 and 16): there the walk is one call a
    product over the experts hit, and stays."""
    wg = bank["wg"]
    return (pairs_landing(rows, spec) > _PAIR_CHUNK
            and expert_mlp_fits(wg.shape[1], wg.shape[2], wg.dtype.itemsize))


def route_grouped(x, router: dict, spec):
    """The grouped, bias-corrected router of DeepSeek-V3
    (`scoring_func` sigmoid, `topk_method` noaux_tc), in f32 whatever
    x is: s = sigmoid(x W_g) over ALL `spec.experts`; s' = s + bias
    chooses and never weighs; the experts lie in `spec.groups` groups,
    a group scores the sum of its two largest s', the `spec.top_groups`
    best groups stay, and among their experts the `spec.top_k` largest
    s' are taken; the weights are the chosen experts' s, normalised to
    sum 1 and times `spec.scale`. router: `gate` (dim, experts) and
    `bias` (experts,), f32. Returns (ids (T, k) int32, weights (T, k)
    f32)."""
    t = x.shape[0]
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router["gate"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    chooser = (s + router["bias"]).reshape(t, spec.groups, -1)
    group_score = jnp.sum(lax.top_k(chooser, 2)[0], axis=-1)
    _, best = lax.top_k(group_score, spec.top_groups)       # (T, top_groups)
    kept = jnp.any(best[:, :, None] == jnp.arange(spec.groups), axis=1)
    chooser = jnp.where(kept[:, :, None], chooser, -jnp.inf)
    _, ids = lax.top_k(chooser.reshape(t, -1), spec.top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * spec.scale
    return ids.astype(jnp.int32), w


def route_softmax(x, router: dict, spec):
    """The plain softmax router (SmallThinker's
    `moe_primary_router_apply_softmax`, `norm_topk_prob`), in f32
    whatever x is: p = softmax(x W_g) over ALL `spec.experts`, the
    `spec.top_k` largest p are taken, and the weights are their p
    normalised to sum 1 and times `spec.scale`. No bias, no groups.
    router: `gate` (dim, experts), f32. Returns (ids (T, k) int32,
    weights (T, k) f32)."""
    p = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), router["gate"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    w, ids = lax.top_k(p, spec.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * spec.scale
    return ids.astype(jnp.int32), w


def route(x, router: dict, spec):
    """(ids, weights) of x's rows by the router `spec` names."""
    if spec.router == "softmax":
        return route_softmax(x, router, spec)
    return route_grouped(x, router, spec)


_GATE_ACT = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def moe_held_inference(x, blk: dict, spec, valid=None, routing=None):
    """One chip's share of a routed expert layer, for INFERENCE: route
    every token over all the layer's experts (`route`, by the router
    `spec` names; or take `routing` = (ids, weights) where the choice
    was made elsewhere, from the layer's input), compute
    the chosen experts whose weights are HERE (`spec.held`, the ids of
    the rows of the banks) and nothing else, add the shared expert
    where the block has one.
    What the absent experts would add is another chip's to compute and
    is left out; nothing stands in for it or for the exchange.

    Only chosen experts are computed: the (token, choice) pairs are
    sorted by held expert — pairs that chose an absent expert, or
    belong to a row outside `valid`, sort last and into no group — and
    the three products of the gated expert MLP run grouped over the
    sorted rows, one group a held expert, in one of two forms that
    `tiled_products` reads off the shapes. Where few pairs can land
    here (dots.vlm1's 16 and 32 of 256 and 512 rows) the rows are
    WALKED in steps of 128, each step three lax.ragged_dot calls (XLA's
    own grouped kernel on the TPU), and only the steps that hold a pair
    are computed. Where many can (smallthinker's 3,072 a chunk, 192 a
    tick) ONE kernel (ops/pallas_expert_mlp) visits every (128-row
    tile, expert) pair that meets and computes the whole gated MLP
    there, an expert's three matrices streamed once however its rows
    fall. Either way shapes are static at tokens x top_k rows, the
    most that can land here, the cost follows the pairs that are
    there, nothing is dropped and no capacity exists: bf16 rows and
    matrices into the MXU, f32 accumulation, the hidden rows rounded to
    the rows' dtype before `wd`, the weights applied in f32 after the
    products. A token's output depends on that token alone.

    x: (T, dim); blk: `router` {gate[, bias]}, `experts` {wg, wu:
    (held, dim, width), wd: (held, width, dim)}, and where the layer
    has one `shared` {wg, wu, wd} (ops/pallas_gemv.swiglu); the gate
    branch's activation is `spec.act`. Returns (y (T, dim), counts
    int32 [pairs computed here, held experts with at least one,
    largest load])."""
    t, k, bank = x.shape[0], spec.top_k, blk["experts"]
    n = len(spec.held)
    ids, w = routing if routing is not None else route(
        x, blk["router"], spec)
    act = _GATE_ACT[spec.act]
    local_of = np.full(spec.experts, n, np.int32)   # n = "not here"
    local_of[list(spec.held)] = np.arange(n)
    local = jnp.asarray(local_of)[ids]                         # (T, k)
    if valid is not None:
        local = jnp.where(valid[:, None], local, n)
    # The walk takes the sorted pairs `chunk` rows at a time, and only
    # the chunks that hold a pair are computed: the grouped kernel pays
    # for every row of its tile in every group, so 512 rows of which 30
    # are pairs cost what 512 pairs cost (PERF.md section 6, PR 28).
    chunk = min(t * k, _PAIR_CHUNK)
    rows = -(-t * k // chunk) * chunk
    flat = jnp.pad(local.reshape(t * k), (0, rows - t * k), constant_values=n)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.sum(flat[:, None] == jnp.arange(n), axis=0,
                    dtype=jnp.int32)                           # (held,)
    ends = jnp.cumsum(sizes)
    xs = x[jnp.minimum(order // k, t - 1)]                     # (rows, dim)
    f32 = dict(preferred_element_type=jnp.float32)

    def one_chunk(j, ys):
        lo = j * chunk
        size = (jnp.clip(ends - lo, 0, chunk)
                - jnp.clip(ends - sizes - lo, 0, chunk))
        xc = lax.dynamic_slice_in_dim(xs, lo, chunk)
        h = (act(lax.ragged_dot(xc, bank["wg"], size, **f32))
             * lax.ragged_dot(xc, bank["wu"], size, **f32))
        yc = lax.ragged_dot(h.astype(x.dtype), bank["wd"], size, **f32)
        return lax.dynamic_update_slice_in_dim(ys, yc, lo, 0)

    if tiled_products(t, spec, bank):
        # Many pairs: one kernel visits every (row tile, expert) that
        # meets, an expert's three matrices streamed once.
        with annotate("ep.held_experts.grouped"):
            ys = expert_mlp(xs, sizes, bank, act, tile=chunk)
    else:
        with annotate("ep.held_experts"):
            ys = lax.fori_loop(0, -(-ends[-1] // chunk), one_chunk,
                               jnp.zeros((rows, x.shape[1]), jnp.float32))
    # Rows past the groups' end belong to no expert: whatever the
    # grouped product left there is not read.
    ys = jnp.where((flat[order] < n)[:, None],
                   ys * jnp.pad(w.reshape(t * k), (0, rows - t * k))[
                       order][:, None], 0.0)
    y = jnp.sum(ys[jnp.argsort(order)[:t * k]].reshape(t, k, -1), axis=1)
    y = y.astype(x.dtype)
    if "shared" in blk:
        y = y + swiglu(x, blk["shared"])
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0), jnp.max(sizes)])
    return y, counts


def make_moe_layer(mesh, *, n_experts, capacity_factor=1.25, axis=EXPERT_AXIS,
                   top_k=1):
    """jitted (params, x) -> (y, aux) with x: (T, D) sharded on `axis` and
    the expert stacks sharded per moe_param_specs — the wrapped EP layer
    for standalone use. Pass full (host) params; shard_map's in_specs
    place each device's expert slice."""

    if n_experts % mesh.shape[axis]:
        raise ValueError(
            f"experts {n_experts} not divisible by {axis!r} size "
            f"{mesh.shape[axis]}"
        )
    body = partial(
        moe_mlp, n_experts=n_experts, capacity_factor=capacity_factor,
        axis=axis, top_k=top_k,
    )

    def shard_body(p_, x_):
        y, aux = body(x_, p_)
        # aux is computed on local tokens; average it so the replicated
        # out_spec is truthful.
        return y, lax.pmean(aux, axis)

    def fn(params, x):
        return jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(moe_param_specs(axis), P(axis)),
            out_specs=(P(axis), P()),
            check_vma=False,
        )(params, x)

    return jax.jit(fn)


def make_ep_lm_train_step(
    model,
    optimizer,
    mesh,
    *,
    data_axis: str | None = None,
    attn_impl: str = "oracle",
    donate: bool = True,
    remat: bool = False,
    moe_aux_weight: float = 0.01,
    compute_dtype=None,
    ce_chunk: int = 0,
    grad_accum: int = 1,
):
    """Expert-parallel LM training WITHOUT a sequence axis — the
    standard Switch/GShard deployment (EP x DP): tokens shard their
    BATCH dim over ('data'?, 'expert') jointly, so attention and every
    dense op run as plain data parallelism across both axes, while each
    MoE block's dispatch all_to_alls tokens to the expert shards over
    'expert' (each rank computes E/P experts; parallel/sp.py's EP x SP
    rides the 'seq' axis instead — this path serves MoE scale when the
    sequence fits one device). Params replicated; grads/loss pmean over
    both axes (different tokens per shard).

    step(state, tokens, targets) -> (state, {"loss": ...}); tokens
    (B, S) int32 with B sharded over (data, expert).
    """
    import optax

    from ..train.lm import get_attn_fn, lm_loss

    if not model.moe_experts:
        raise ValueError(
            "an 'expert' mesh axis needs an MoE model (--moe-experts); "
            "for dense models the axis is just data parallelism — use "
            "a 'data' axis"
        )
    n_exp = mesh.shape[EXPERT_AXIS]
    if model.moe_experts % n_exp:
        raise ValueError(
            f"experts {model.moe_experts} not divisible by expert-axis "
            f"size {n_exp}"
        )
    attn_fn = get_attn_fn(attn_impl)
    reduce_axes = tuple(a for a in (data_axis, EXPERT_AXIS) if a)

    def step(state, tokens, targets):
        # dp.py's shared accumulation; the dispatch all_to_alls run
        # uniformly per micro-batch on every rank. Per-micro-batch
        # expert capacity is a (documented) estimator change, exactly
        # like every microbatched MoE trainer.
        if grad_accum > 1 and tokens.shape[0] % grad_accum:
            raise ValueError(
                f"per-shard batch {tokens.shape[0]} not divisible by "
                f"grad_accum {grad_accum}"
            )
        from .dp import local_grads_no_aux

        loss, grads = local_grads_no_aux(
            lambda p, t, g: lm_loss(
                model, p, t, g, attn_fn=attn_fn,
                compute_dtype=compute_dtype, remat=remat,
                moe_aux_weight=moe_aux_weight, ce_chunk=ce_chunk,
                moe_axis=EXPERT_AXIS,
            ),
            state["params"], tokens, targets, grad_accum,
        )
        grads = lax.pmean(grads, reduce_axes)
        loss = lax.pmean(loss, reduce_axes)
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        params = optax.apply_updates(state["params"], updates)
        return (
            {"params": params, "opt_state": opt_state,
             "step": state["step"] + 1},
            {"loss": loss},
        )

    bspec = P((data_axis, EXPERT_AXIS) if data_axis else EXPERT_AXIS)
    sharded = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), bspec, bspec),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return donate_jit(sharded, donate=donate)
