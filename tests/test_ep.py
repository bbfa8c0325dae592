"""Expert parallelism (parallel/ep.py): routing semantics + EP parity +
training integration on the 8-virtual-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from mpi_cuda_cnn_tpu.parallel.ep import (
    EXPERT_AXIS,
    init_moe_params,
    make_moe_layer,
    moe_mlp,
    top1_dispatch,
)
from mpi_cuda_cnn_tpu.parallel.mesh import make_mesh

D, H, E = 16, 32, 8


def _params(seed=0):
    return init_moe_params(jax.random.key(seed), D, H, E)


def _tokens(t=64, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal((t, D)), jnp.float32
    )


def _mesh(n=8):
    return make_mesh({EXPERT_AXIS: n}, devices=jax.devices()[:n])


# ---------------------------------------------------------------------------
# Routing semantics
# ---------------------------------------------------------------------------


def test_dispatch_at_most_one_slot_per_token():
    x, p = _tokens(), _params()
    dispatch, combine, _ = top1_dispatch(x, p["gate"], E, capacity=16)
    per_token = np.asarray(jnp.sum(dispatch, axis=(1, 2)))
    assert set(np.unique(per_token)) <= {0.0, 1.0}
    # combine = dispatch * gate, gate in (0, 1]
    assert np.all(np.asarray(jnp.sum(combine, axis=(1, 2))) <= per_token + 1e-6)


def test_dispatch_respects_capacity():
    x, p = _tokens(t=256), _params()
    cap = 4
    dispatch, _, _ = top1_dispatch(x, p["gate"], E, capacity=cap)
    per_expert = np.asarray(jnp.sum(dispatch, axis=(0, 2)))
    assert np.all(per_expert <= cap)
    # Each (expert, slot) pair holds at most one token.
    per_slot = np.asarray(jnp.sum(dispatch, axis=0))
    assert per_slot.max() <= 1.0 + 1e-6


def test_overflow_tokens_get_zero_output():
    """With capacity 1, most tokens drop: their MoE output must be 0."""
    x, p = _tokens(t=64), _params()
    dispatch, _, _ = top1_dispatch(x, p["gate"], E, capacity=1)
    y, _ = moe_mlp(x, p, n_experts=E, capacity_factor=E / 64.0, axis=None)
    kept = np.asarray(jnp.sum(dispatch, axis=(1, 2))) > 0
    dropped_rows = np.asarray(y)[~kept]
    np.testing.assert_allclose(dropped_rows, 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# EP parity: the all_to_all relocation must not change the math
# ---------------------------------------------------------------------------


def test_ep_matches_per_shard_oracle():
    mesh = _mesh()
    p = _params()
    t_global = 8 * 16
    x = _tokens(t=t_global, seed=2)
    layer = make_moe_layer(mesh, n_experts=E)
    y_ep, aux_ep = layer(p, x)

    # Oracle: identical routing runs per shard (EP only relocates the
    # expert compute), dense experts on one device.
    shards = np.split(np.asarray(x), 8)
    outs, auxes = [], []
    for sh in shards:
        y, aux = moe_mlp(jnp.asarray(sh), p, n_experts=E, axis=None)
        outs.append(np.asarray(y))
        auxes.append(float(aux))
    np.testing.assert_allclose(
        np.asarray(y_ep), np.concatenate(outs), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(float(aux_ep), np.mean(auxes), rtol=1e-5)


def test_ep_rejects_indivisible_experts():
    mesh = _mesh()
    with pytest.raises(ValueError, match="experts"):
        make_moe_layer(mesh, n_experts=6)  # 6 % 8 != 0


# ---------------------------------------------------------------------------
# Training integration: gradients flow through routing + all_to_all
# ---------------------------------------------------------------------------


def test_ep_layer_trains():
    """Tiny regression task through the EP layer: loss must drop and all
    param groups (gate included) must receive gradients."""
    mesh = _mesh()
    params = _params(seed=3)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((128, D)), jnp.float32)
    target = jnp.asarray(np.roll(np.asarray(x), 1, axis=1))

    from jax.sharding import PartitionSpec as P
    from functools import partial as _partial
    from mpi_cuda_cnn_tpu.parallel.ep import moe_mlp as _moe, moe_param_specs

    def loss_fn(params, x, target):
        body = _partial(_moe, n_experts=E, axis=EXPERT_AXIS)

        def shard_body(p_, x_, t_):
            y, aux = body(x_, p_)
            local = jnp.mean((y - t_) ** 2)
            return (jax.lax.pmean(local, EXPERT_AXIS)
                    + 0.01 * jax.lax.pmean(aux, EXPERT_AXIS))

        return jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(moe_param_specs(), P(EXPERT_AXIS), P(EXPERT_AXIS)),
            out_specs=P(), check_vma=False,
        )(params, x, target)

    step = jax.jit(jax.value_and_grad(loss_fn))
    losses = []
    for _ in range(60):
        loss, grads = step(params, x, target)
        for leaf in jax.tree.leaves(grads):
            assert np.all(np.isfinite(np.asarray(leaf)))
        updates, opt_state = opt.update(grads, opt_state)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.5, f"no learning: {losses[::15]}"


# ---------------------------------------------------------------------------
# Top-k (k=2) routing — round-2 item 9
# ---------------------------------------------------------------------------


def test_topk_k1_matches_top1_exactly():
    from mpi_cuda_cnn_tpu.parallel.ep import topk_dispatch

    x, p = _tokens(t=64), _params()
    d1, c1, a1 = top1_dispatch(x, p["gate"], E, capacity=16)
    dk, ck, ak = topk_dispatch(x, p["gate"], E, capacity=16, k=1)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(dk))
    np.testing.assert_allclose(np.asarray(c1), np.asarray(ck), atol=1e-7)
    assert float(a1) == pytest.approx(float(ak))


def test_top2_dispatch_invariants():
    from mpi_cuda_cnn_tpu.parallel.ep import topk_dispatch

    x, p = _tokens(t=128), _params()
    cap = 40
    dispatch, combine, _ = topk_dispatch(x, p["gate"], E, capacity=cap, k=2)
    d = np.asarray(dispatch)
    # Each token occupies at most 2 slots, in 2 DIFFERENT experts.
    per_token = d.sum(axis=(1, 2))
    assert per_token.max() <= 2.0 + 1e-6
    per_token_expert = d.sum(axis=2)
    assert per_token_expert.max() <= 1.0 + 1e-6
    # Each (expert, slot) pair holds at most one token; capacity respected.
    assert d.sum(axis=0).max() <= 1.0 + 1e-6
    assert d.sum(axis=(0, 2)).max() <= cap
    # Combined gates are renormalized: a fully-kept token's combine sums
    # to ~1 (both choices kept), a half-dropped one to < 1.
    kept_both = per_token >= 2.0 - 1e-6
    csum = np.asarray(combine).sum(axis=(1, 2))
    np.testing.assert_allclose(csum[kept_both], 1.0, atol=1e-5)
    assert np.all(csum <= 1.0 + 1e-5)


def test_top2_first_choices_never_evicted():
    """Choice-priority capacity: adding 2nd choices must not change which
    FIRST choices are kept."""
    from mpi_cuda_cnn_tpu.parallel.ep import topk_dispatch

    x, p = _tokens(t=128), _params()
    cap = 8
    d1, _, _ = topk_dispatch(x, p["gate"], E, capacity=cap, k=1)
    d2, _, _ = topk_dispatch(x, p["gate"], E, capacity=cap, k=2)
    probs = jax.nn.softmax(x @ p["gate"], axis=-1)
    first = np.asarray(jnp.argmax(probs, axis=-1))
    # Project d2 onto first-choice experts only.
    d2_first = np.asarray(d2).sum(axis=2)[np.arange(128), first]
    d1_first = np.asarray(d1).sum(axis=2)[np.arange(128), first]
    np.testing.assert_array_equal(d1_first, d2_first)


def test_top2_ep_matches_oracle():
    """Sharded top-2 EP layer == the axis=None oracle on the same tokens."""
    mesh = _mesh()
    p = _params()
    x = _tokens(t=8 * 16, seed=4)
    layer = make_moe_layer(mesh, n_experts=E, top_k=2)
    y_ep, aux_ep = layer(p, x)
    y_or, aux_or = moe_mlp(x, p, n_experts=E, axis=None, top_k=2)
    # The sharded layer routes per device shard (16 tokens each) while the
    # oracle routes globally — compare per-shard oracles.
    ys = []
    for s in range(8):
        y_s, _ = moe_mlp(x[s * 16:(s + 1) * 16], p, n_experts=E, axis=None,
                         top_k=2)
        ys.append(np.asarray(y_s))
    np.testing.assert_allclose(
        np.asarray(y_ep), np.concatenate(ys), rtol=1e-5, atol=1e-5
    )


def test_top2_moe_lm_trains():
    """A top-2 MoE TransformerLM trains end to end under SP x EP."""
    import optax as _optax

    from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
    from mpi_cuda_cnn_tpu.parallel.sp import SEQ_AXIS, make_sp_lm_train_step

    mesh = make_mesh({SEQ_AXIS: 4}, devices=jax.devices()[:4])
    lm = TransformerLM(vocab=17, dim=32, heads=4, depth=2, max_seq=64,
                       moe_experts=4, moe_top_k=2)
    params = lm.init(jax.random.key(0))
    opt = _optax.adam(3e-3)
    state = {"params": params, "opt_state": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    step = make_sp_lm_train_step(lm, opt, mesh)
    rng = np.random.default_rng(0)
    start = rng.integers(0, 17, size=(4, 1))
    toks = jnp.asarray((start + np.arange(65)) % 17, jnp.int32)
    losses = []
    for _ in range(40):
        state, m = step(state, toks[:, :-1], toks[:, 1:])
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.7


def test_top2_inference_weights_two_experts():
    from mpi_cuda_cnn_tpu.parallel.ep import moe_mlp_inference

    x, p = _tokens(t=16), _params()
    y1 = moe_mlp_inference(x, p, n_experts=E, top_k=1)
    y2 = moe_mlp_inference(x, p, n_experts=E, top_k=2)
    assert y1.shape == y2.shape == x.shape
    # k=2 mixes a second expert: outputs must differ from pure top-1.
    assert float(jnp.max(jnp.abs(y1 - y2))) > 1e-4


def test_ep_dp_lm_trains(eight_devices):
    """EP x DP WITHOUT a sequence axis (parallel/ep.py
    make_ep_lm_train_step — the standard Switch deployment): batch
    sharded over (data, expert) jointly, MoE dispatch all_to_alling
    over 'expert'; the product loop trains, eval/decode work off the
    replicated state, and the composition/requirement checks fail
    loudly."""
    import pytest

    from mpi_cuda_cnn_tpu.train.lm_trainer import LMTrainer
    from mpi_cuda_cnn_tpu.utils.config import LMConfig
    from mpi_cuda_cnn_tpu.utils.logging import MetricsLogger

    base = dict(corpus="synthetic", dim=32, depth=2, heads=4, seq_len=64,
                steps=8, batch_size=8, log_every=0,
                lr_schedule="constant", warmup_steps=0, sample_tokens=4)
    t = LMTrainer(LMConfig(mesh_shape="data:2,expert:4", moe_experts=4,
                           **base), metrics=MetricsLogger(echo=False))
    r = t.train()
    assert r.steps_run == 8 and np.isfinite(r.eval_ppl)
    _, cont = t.sample(4)
    assert len(cont) == 4

    # --grad-accum rides the EP shard_map too (per-micro-batch capacity
    # is the documented estimator change).
    t2 = LMTrainer(LMConfig(mesh_shape="data:2,expert:2", moe_experts=4,
                            grad_accum=2, **base),
                   metrics=MetricsLogger(echo=False))
    r2 = t2.train()
    assert r2.steps_run == 8 and np.isfinite(r2.final_loss)

    with pytest.raises(ValueError, match="expert"):  # dense model
        LMTrainer(LMConfig(mesh_shape="expert:4", **base),
                  metrics=MetricsLogger(echo=False))
    with pytest.raises(ValueError, match="composes with 'data' only"):
        LMTrainer(LMConfig(mesh_shape="expert:2,seq:2", moe_experts=4,
                           **base), metrics=MetricsLogger(echo=False))
    # --moe-dispatch-dtype is threaded only through the plain jitted
    # step; the shard_map meshes must reject it rather than silently
    # building f32 dispatch tensors.
    with pytest.raises(ValueError, match="moe-dispatch-dtype"):
        LMTrainer(LMConfig(mesh_shape="data:2,expert:4", moe_experts=4,
                           moe_dispatch_dtype="bfloat16", **base),
                  metrics=MetricsLogger(echo=False))


# ---------------------------------------------------------------------------
# Chunked dispatch (the single-chip quadratic-dispatch lever)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("top_k", [1, 2])
def test_dispatch_chunk_matches_unchunked_when_nothing_drops(top_k):
    """With capacity ample enough that no token drops, per-chunk routing
    assigns every token to the same expert with the same gate as
    whole-batch routing — the same outputs (routing is per-token;
    capacity boundaries are the ONLY coupling between tokens, and the
    fused router's gate reassociation is exact — each token's expert
    rows hold one occupied slot each). Equal to an ulp or two, not
    bitwise, for top-1 as for top-2: the expert matmuls and the combine
    contract over capacity extents of 64 rows in one case and 16 in the
    other, and the backend tiles and orders the two differently (on
    this CPU backend 731 of 1024 top-1 elements differ, by <= 2.4e-7)."""
    p = _params()
    x = _tokens(64)
    want, want_aux = moe_mlp(x, p, n_experts=E, capacity_factor=8.0,
                             axis=None, top_k=top_k)
    got, got_aux = moe_mlp(x, p, n_experts=E, capacity_factor=8.0,
                           axis=None, top_k=top_k, dispatch_chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-7, atol=3e-7)
    # aux is the GLOBAL balance loss formed once from count/prob sums
    # accumulated across the chunk scan — the same objective as
    # unchunked routing, agreeing to float summation-order rounding
    # (the old per-chunk-mean form was a biased estimator and needed a
    # 0.2-absolute band here).
    assert float(got_aux) == pytest.approx(float(want_aux), rel=1e-5,
                                           abs=1e-6)


def test_router_dispatch_fused_equals_dense_pair():
    """router_dispatch's (dispatch, gate_te) fused form must reproduce
    the dense (dispatch, combine) pair exactly: combine == dispatch *
    gate_te (distinct chosen experts put at most one choice's gate on
    any (t, e) pair)."""
    from mpi_cuda_cnn_tpu.parallel.ep import router_dispatch, topk_dispatch

    x, p = _tokens(t=128), _params()
    for k in (1, 2):
        d, c, a = topk_dispatch(x, p["gate"], E, capacity=24, k=k)
        df, gte, af = router_dispatch(x, p["gate"], E, 24, k=k,
                                      dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(d), np.asarray(df))
        np.testing.assert_array_equal(
            np.asarray(c), np.asarray(df * gte[:, :, None])
        )
        assert float(a) == pytest.approx(float(af))


def test_dispatch_chunk_no_batch_extent_routing_alloc():
    """ISSUE 2 front 2, asserted mechanically: the compiled CHUNKED MoE
    program must never allocate a routing tensor at batch extent — its
    live scratch (XLA memory analysis temp bytes) stays below one
    (T, E, C_full) f32 tensor, while the unchunked program's scratch is
    at least that (it materializes the batch-extent dispatch)."""
    p = _params()
    t, chunk = 512, 64
    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((t, D)), jnp.float32
    )
    cap_full = max(1, -int(-t * 2 * 1.25 // E))
    tec_bytes = t * E * cap_full * 4

    def temp_bytes(dc):
        f = jax.jit(lambda x, p: moe_mlp(
            x, p, n_experts=E, axis=None, top_k=2, dispatch_chunk=dc
        ))
        ma = f.lower(x, p).compile().memory_analysis()
        assert ma is not None, "backend exposes no memory analysis"
        return int(ma.temp_size_in_bytes)

    assert temp_bytes(chunk) < tec_bytes, (
        "chunked MoE step allocates batch-extent routing scratch"
    )
    # The contrast that proves the method — only meaningful while this
    # XLA:CPU materializes the unchunked batch-extent dispatch (true on
    # the measured 0.4.37; a future compiler that fuses it away would
    # invalidate the contrast, not the guarantee above).
    if jax.__version__ == "0.4.37":
        assert temp_bytes(0) >= tec_bytes


def test_dispatch_chunk_capacity_is_per_chunk():
    """At tight capacity the chunked form drops per chunk: a token
    burst routed to one expert overflows a whole-batch queue but fits
    per-chunk queues — the documented estimator change, visible as
    different outputs, both finite."""
    p = _params()
    x = _tokens(64, seed=3)
    y_whole, _ = moe_mlp(x, p, n_experts=E, capacity_factor=0.25,
                         axis=None)
    y_chunk, _ = moe_mlp(x, p, n_experts=E, capacity_factor=0.25,
                         axis=None, dispatch_chunk=16)
    assert np.isfinite(np.asarray(y_whole)).all()
    assert np.isfinite(np.asarray(y_chunk)).all()


def test_dispatch_chunk_rejections():
    p = _params()
    x = _tokens(64)
    with pytest.raises(ValueError, match="EP"):
        moe_mlp(x, p, n_experts=E, axis=EXPERT_AXIS, dispatch_chunk=16)
    with pytest.raises(ValueError, match="divisible"):
        moe_mlp(x, p, n_experts=E, axis=None, dispatch_chunk=60)


def test_dispatch_chunk_grads_flow_and_lm_step_runs():
    """The chunked path differentiates (scan grads) and is reachable
    from the LM train step (make_lm_train_step moe_dispatch_chunk)."""
    from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
    from mpi_cuda_cnn_tpu.train.lm import make_lm_state, make_lm_train_step

    p = _params()
    x = _tokens(32)

    def loss(p, x):
        y, aux = moe_mlp(x, p, n_experts=E, axis=None, top_k=2,
                         dispatch_chunk=16)
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(p, x)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))

    model = TransformerLM(vocab=32, dim=16, heads=2, depth=1, max_seq=32,
                          moe_experts=2, moe_top_k=2)
    opt = optax.sgd(0.1)
    step = make_lm_train_step(model, opt, attn_impl="oracle", seq_len=16,
                              donate=False, moe_dispatch_chunk=8)
    state = make_lm_state(model, opt, 0)
    toks = jnp.asarray(
        np.random.default_rng(7).integers(0, 32, (2, 17)), jnp.int32
    )
    state, m = step(state, toks[:, :-1], toks[:, 1:])
    assert np.isfinite(float(m["loss"]))
