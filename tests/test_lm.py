"""LM training path (train/lm.py): mixed precision, attention impl
selection, and the single-device train step the MFU bench runs.

The SP (sharded) LM step is covered by test_transformer.py; this file
covers the plain jitted step and the bf16 numerics contract: master
params f32, matmuls in compute_dtype, loss softmax in f32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi_cuda_cnn_tpu.models.transformer import TransformerLM
from mpi_cuda_cnn_tpu.train.lm import (
    get_attn_fn,
    lm_flops_per_token,
    lm_loss,
    make_lm_state,
    make_lm_train_step,
    pick_attn_impl,
)
from mpi_cuda_cnn_tpu.train.optimizer import make_optimizer

MODEL = TransformerLM(vocab=31, dim=32, heads=4, depth=2, max_seq=128)


def _data(batch=4, s=128, seed=0):
    rng = np.random.default_rng(seed)
    start = rng.integers(0, MODEL.vocab, size=(batch, 1))
    toks = (start + np.arange(s + 1)[None, :]) % MODEL.vocab
    return (jnp.asarray(toks[:, :-1], jnp.int32),
            jnp.asarray(toks[:, 1:], jnp.int32))


def test_bf16_loss_close_to_f32():
    params = MODEL.init(jax.random.key(0))
    tokens, targets = _data()
    l32 = float(lm_loss(MODEL, params, tokens, targets))
    lbf = float(lm_loss(MODEL, params, tokens, targets,
                        compute_dtype=jnp.bfloat16))
    assert abs(l32 - lbf) < 0.05 * abs(l32)


def test_bf16_keeps_master_params_f32():
    """A bf16 step must update f32 master params (mixed precision, not
    low-precision storage)."""
    opt = make_optimizer(1e-3, opt="adamw")
    step = make_lm_train_step(MODEL, opt, attn_impl="oracle",
                              compute_dtype=jnp.bfloat16, donate=False)
    state = make_lm_state(MODEL, opt, 0)
    state2, m = step(state, *_data())
    assert jnp.isfinite(m["loss"])
    for leaf in jax.tree.leaves(state2["params"]):
        assert leaf.dtype == jnp.float32
    # And the params actually moved.
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))),
        state["params"], state2["params"],
    )
    assert max(jax.tree.leaves(moved)) > 0


def test_train_step_learns_cyclic_task():
    """200 AdamW steps on the deterministic successor task should drive
    the loss near zero — the step optimizes, not just runs."""
    opt = make_optimizer(3e-3, opt="adamw")
    step = make_lm_train_step(MODEL, opt, attn_impl="oracle")
    state = make_lm_state(MODEL, opt, 0)
    tokens, targets = _data()
    for _ in range(200):
        state, m = step(state, tokens, targets)
    assert float(m["loss"]) < 0.3


def test_flash_impl_matches_oracle_in_step():
    """One train step with the fused flash kernel (interpret mode on CPU)
    == one step with the quadratic oracle."""
    opt = make_optimizer(1e-3, opt="adamw")
    tokens, targets = _data(batch=2, s=128)
    outs = {}
    for impl in ("oracle", "flash"):
        step = make_lm_train_step(MODEL, opt, attn_impl=impl, donate=False)
        state = make_lm_state(MODEL, opt, 0)
        state, m = step(state, tokens, targets)
        outs[impl] = (float(m["loss"]), state["params"])
    assert outs["oracle"][0] == pytest.approx(outs["flash"][0], rel=1e-4)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
        ),
        outs["oracle"][1], outs["flash"][1],
    )


def test_pick_attn_impl(monkeypatch):
    # On the CPU test backend "auto" must not pick the interpret-mode
    # flash kernel (orders of magnitude slower than XLA).
    assert pick_attn_impl("auto", 2048) == "oracle"
    assert pick_attn_impl("flash", 2048) == "flash"
    with pytest.raises(ValueError):
        get_attn_fn("nope")


def test_flash_attn_fn_on_a_data_mesh_matches_single_device(eight_devices):
    """GSPMD cannot partition a Mosaic kernel (the first four-chip run,
    PR 21), so on a multi-device mesh get_attn_fn wraps the flash kernel
    in a shard_map over the batch. Rows are independent, so the wrapped
    kernel must equal the bare one — with the batch split over 'data',
    and replicated when 'data' does not divide it or is absent."""
    from mpi_cuda_cnn_tpu.parallel.mesh import make_mesh

    rng = np.random.default_rng(0)
    bare = get_attn_fn("flash")
    for axes, batch in (({"data": 2}, 2), ({"data": 2}, 3),
                        ({"model": 2}, 2)):
        mesh = make_mesh(axes, devices=eight_devices[:2])
        q, k, v = (jnp.asarray(rng.normal(size=(batch, 128, 2, 16)),
                               jnp.float32) for _ in range(3))
        got = jax.jit(get_attn_fn("flash", mesh))(q, k, v)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(bare(q, k, v)))
    assert get_attn_fn("flash", make_mesh(
        {"data": 1}, devices=eight_devices[:1])).__name__ == "flash"


def test_pick_attn_impl_routing_table(monkeypatch):
    """Pin "auto" to the measured crossovers (one v5e): bf16 -> flash at
    any 128-aligned s (wins 2.2x at s=2048, round-4 capture: 56.4 vs
    125.7 ms/step); f32 -> flash from s=3072 up (round-4
    bench_crossover, two captures: flash wins both runs at every point
    in {3072, 4096, 6144}; s=2048 flips run-to-run, so it routes to the
    oracle with the rest of the short/noise band); unaligned s ->
    oracle always."""
    from mpi_cuda_cnn_tpu.train import lm as lm_mod

    monkeypatch.setattr(lm_mod.jax, "default_backend", lambda: "tpu")
    bf16 = jnp.bfloat16
    assert pick_attn_impl("auto", 2048, bf16) == "flash"
    assert pick_attn_impl("auto", 128, bf16) == "flash"
    assert pick_attn_impl("auto", 1024, None) == "oracle"       # f32 short
    assert pick_attn_impl("auto", 2048, None) == "oracle"       # f32 flip zone
    assert pick_attn_impl("auto", 2048, jnp.float32) == "oracle"
    assert pick_attn_impl("auto", 3072, None) == "flash"        # f32 crossover
    assert pick_attn_impl("auto", 4096, None) == "flash"        # f32 long
    assert pick_attn_impl("auto", 8192, jnp.float32) == "flash"
    assert pick_attn_impl("auto", 2000, bf16) == "oracle"       # unaligned
    # Explicit impls are never overridden.
    assert pick_attn_impl("oracle", 8192, bf16) == "oracle"
    assert pick_attn_impl("flash", 2048, None) == "flash"


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16])
def test_chunked_ce_matches_dense(dtype):
    """ce_chunk fuses the head into a scanned chunked cross-entropy; it
    must be an implementation choice, not a different loss: value AND
    gradients match the dense (B,S,V)-logits path."""
    from mpi_cuda_cnn_tpu.train.lm import lm_loss

    params = MODEL.init(jax.random.key(1))
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, MODEL.vocab, (2, 33)), jnp.int32)
    tokens, targets = toks[:, :-1], toks[:, 1:]

    def loss(ce_chunk):
        return lambda p: lm_loss(
            MODEL, p, tokens, targets, compute_dtype=dtype,
            ce_chunk=ce_chunk,
        )

    tol = dict(rtol=2e-5, atol=1e-6) if dtype is None else \
        dict(rtol=2e-2, atol=2e-3)
    l_dense, g_dense = jax.value_and_grad(loss(0))(params)
    l_chunk, g_chunk = jax.value_and_grad(loss(8))(params)
    np.testing.assert_allclose(float(l_dense), float(l_chunk), **tol)
    for a, b in zip(jax.tree.leaves(g_dense), jax.tree.leaves(g_chunk)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)

    with pytest.raises(ValueError, match="must divide"):
        loss(7)(params)


def test_flops_accounting_scales():
    small = lm_flops_per_token(MODEL, 128)
    # Double depth ~= double the per-layer FLOPs share.
    deep = lm_flops_per_token(
        TransformerLM(vocab=31, dim=32, heads=4, depth=4, max_seq=128), 128
    )
    assert deep > small
    # fwd+bwd = 3x fwd: per-token FLOPs must exceed 6x params-ex-embedding.
    d, l = MODEL.dim, MODEL.depth
    assert small > 6 * (12 * d * d) * l
