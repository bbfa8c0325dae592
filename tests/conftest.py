"""Test harness config: run everything on a virtual 8-device CPU mesh.

Must run before the first `import jax` anywhere in the test process —
pytest imports conftest.py first, so setting the env here is sufficient
(SURVEY.md §4: multi-device DP tests runnable without a TPU).
"""

import os

# Force CPU regardless of ambient JAX_PLATFORMS — the suite must run
# identically on a TPU host and a plain CI box; the chip is covered by
# chip_smoke.py. The env var is enough when it is set before jax is
# imported, which conftest guarantees for the test process; the config
# update below additionally covers a process that imported jax earlier
# (no backend is initialized yet at conftest time, so it still applies).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Tests measured >=~7s on the CI box (pytest --durations, re-measured
# 2026-07-31). Skipped by default so the round-trip suite stays fast;
# `--runslow` (or `make test_all`) runs everything. Every subsystem
# keeps at least one fast representative in the default set — this list
# only trims the heavy variants (the biggest parity matrices, e2e
# trainer loops, multihost spawns).
SLOW_TESTS = {
    # Round-4 trim (VERDICT r3 item 8: the fast set missed the 5-min
    # bar): heaviest fast tests by measured duration, each with a fast
    # twin remaining — e.g. pp_lm keeps step_matches_serial[mesh_axes0] +
    # ce_chunk parity; tp_sp keeps step_matches_serial[0-learned-
    # mesh_axes0]; tp_pp_lm keeps its mesh_axes0 parity + rejects.
    "test_pp_lm.py::test_lm_trainer_pipeline_e2e",
    "test_pp_lm.py::test_pp_lm_flash_matches_oracle",
    "test_tp_sp.py::test_tp_sp_ring_flash_matches_serial",
    "test_tp_sp.py::test_tp_sp_grad_clip_matches_serial",
    "test_tp_sp.py::test_lm_trainer_tp_sp_e2e",
    "test_lm.py::test_chunked_ce_matches_dense[None]",
    "test_transformer.py::test_sp_step_with_chunked_ce_matches_dense",
    "test_tp_pp.py::test_tp_pp_pack_unpack_roundtrip",
    "test_tp_pp.py::test_trainer_fsdp_tp_matches_pure_dp",
    "test_models.py::test_presets_init_and_apply[lenet5]",
    "test_lm_trainer.py::test_sample_generates_within_budget",
    "test_pp.py::test_pp_loss_and_grads_match_serial[4-8]",
    "test_golden_c.py::test_c_lm_flags_reach_the_lm_trainer",
    "test_gqa_rope.py::test_lm_variants_train_and_decode[2-rope]",
    "test_pallas.py::test_conv_grad_parity[4-28-28-1-3-16-2-1]",
    "test_tp_pp_lm.py::test_tp_pp_lm_step_matches_serial[mesh_axes1-0-learned]",
    "test_tp_pp_lm.py::test_tp_pp_lm_step_matches_serial[mesh_axes2-2-rope]",
    "test_tp_pp_lm.py::test_tp_pp_lm_grad_clip_and_ce_chunk_match_serial",
    "test_tp_pp_lm.py::test_lm_trainer_tp_pp_e2e",
    # Second-tier trim to land the 1-2-core serial bar; every moved test
    # leaves a faster sibling covering the same subsystem (LM TP parity
    # additionally runs in the driver's dryrun path 9 on every round).
    "test_tp.py::test_lm_tp_state_is_sharded_and_step_matches_serial",
    "test_lm_trainer.py::test_cli_lm_subcommand",
    "test_attention.py::test_ring_flash_gradients_match_oracle",
    "test_lm.py::test_bf16_keeps_master_params_f32",
    "test_models.py::test_residual_odd_spatial_downsample",
    "test_pp.py::test_pp_composes_with_dp",
    "test_pp.py::test_pp_grad_clip_matches_optax[mesh_axes0-1-False]",
    "test_train.py::test_scan_chunked_logging",
    "test_train.py::test_bfloat16_training",
    "test_gqa_rope.py::test_lm_variants_train_and_decode[1-rope]",
    "test_pallas.py::test_conv_forward_parity[4-14-14-16-3-32-2-1]",
    "test_pallas.py::test_conv_forward_parity[4-28-28-1-3-16-2-1]",
    "test_tp.py::test_tp_trainer_end_to_end[False]",
    "test_tp.py::test_tp_trainer_matches_dp_trainer",
    "test_fsdp.py::test_fsdp_pp_matches_plain_pp[False-pipe:2,model:2,data:2]",
    # test_pp_lm_grad_clip_matches_serial stays FAST: the LM in-step
    # clip-norm assembly needs a default-suite representative (the
    # tp_sp/tp_pp_lm clip tests here are its slow siblings).
    "test_pp_lm.py::test_pp_lm_ce_chunk_matches_dense",
    "test_pp_lm.py::test_pp_lm_moe_single_microbatch_matches_serial",
    "test_flash_attention.py::test_flash_gradients_match_oracle[512-True]",
    "test_fsdp.py::test_lm_trainer_fsdp_sp_e2e",
    # Both FSDP x SP parity variants are slow; the driver's dryrun path
    # 13 runs the same step with a serial-parity assert every round, so
    # the composition keeps default-gate coverage outside pytest.
    "test_fsdp.py::test_lm_fsdp_sp_matches_replicated_sp[0.05]",
    "test_fsdp.py::test_lm_fsdp_sp_matches_replicated_sp[0.0]",
    "test_fsdp.py::test_lm_fsdp_step_matches_replicated",
    "test_pp_lm.py::test_sp_pp_lm_step_matches_serial[mesh_axes1]",
    "test_pp_lm.py::test_lm_trainer_sp_pp_e2e",
    "test_pp_lm.py::test_sp_pp_lm_moe_trains",
    # The 4D mesh runs in the driver's dryrun path 15 (serial-parity
    # asserted) every round besides these slow twins; the 16-device
    # all-four-axes composition is a spawned worker (own jax process).
    "test_4d_full.py::test_full_4d_mesh_16_devices_matches_serial",
    "test_tp_pp_lm.py::test_tp_pp_lm_4d_matches_serial",
    "test_tp_pp_lm.py::test_lm_trainer_4d_e2e",
    "test_tp_pp_lm.py::test_tp_pp_lm_checkpoint_resume",
    "test_step_resume.py::test_mid_epoch_resume_under_mesh[data:8]",
    # Elasticity (ISSUE 5): the CNN cross-width e2e variants and the
    # preemption mechanics stay fast; these two heavy twins run in the
    # explicit CI elasticity step (named ::-exactly, which overrides
    # this skip) and under --runslow.
    "test_elastic.py::test_lm_preempt_resume_across_widths_bitwise",
    "test_elastic.py::test_elastic_step_is_width_invariant_and_pmean_is_not",
    "test_elastic.py::test_elastic_augment_keys_on_canonical_shard",
    # Fleet (ISSUE 7): the tier-1-size storm + lifecycle/fencing tests
    # stay fast; the 10^5-request acceptance storm and the engine-backed
    # (jit-compiling) crash-parity twins run in the explicit CI fleet
    # step (named ::-exactly, which overrides this skip) and --runslow.
    "test_fleet.py::test_storm_100k_scale",
    "test_fleet.py::test_engine_fleet_crash_outputs_match_crash_free[resume]",
    "test_fleet.py::test_engine_fleet_crash_outputs_match_crash_free[discard]",
    # Disaggregated serving (ISSUE 13): same split — the tier-1-size
    # 2-pool storms, crash/corruption/degradation mechanics, and the
    # fast engine parity twin stay fast; the 10^5 acceptance storm and
    # the prefix-through-handoff engine parity run in the explicit CI
    # disagg step (named ::-exactly) and --runslow.
    "test_disagg.py::test_disagg_storm_100k_scale",
    "test_disagg.py::test_engine_disagg_outputs_match_unified_through_handoff[True]",
    # Speculative serving (ISSUE 14): the f32 bitwise parity, the
    # preemption+prefix composition, the tick-drop pin, the scheduler
    # rollback invariants, the sim-fleet parity, and the obs/CLI
    # round-trips stay fast; these heavy engine-compile twins (bf16/
    # int8 dtype matrix, the draft proposer, the engine-backed crash
    # and disagg-handoff parity legs) run in the explicit CI serving
    # step (named ::-exactly, which overrides this skip) and --runslow.
    "test_spec_serve.py::test_engine_spec_on_off_bitwise_parity[bfloat16]",
    "test_spec_serve.py::test_engine_spec_on_off_bitwise_parity[int8]",
    "test_spec_serve.py::test_engine_spec_draft_parity",
    "test_spec_serve.py::test_engine_fleet_spec_crash_parity",
    "test_spec_serve.py::test_engine_disagg_spec_parity_through_handoff",
    # Flight recorder (ISSUE 15): the engine/fleet/disagg replay
    # mechanics, tamper/legacy/diverge pins, and gate wiring stay
    # fast; the two reduced-scale storm twins of the CI determinism
    # gates (--spec lookup, --pools at 20k requests, full-log) run in
    # the explicit CI obs step (named ::-exactly) and --runslow — the
    # full-scale fleet storm replay is its own CI step.
    "test_replay.py::test_replay_spec_storm_twin",
    "test_replay.py::test_replay_disagg_storm_twin",
    # Host-tier spill (ISSUE 17): the engine/fleet parity legs, the
    # corrupt-refusal degradation, the bounded-LRU/CRC unit mechanics,
    # and the replay round-trips stay fast; the 10^5-request
    # determinism storm runs in the explicit CI serving step (named
    # ::-exactly, which overrides this skip) and --runslow.
    "test_host_tier.py::test_spill_determinism_storm_1e5_twice_bitwise",
    "test_models.py::test_residual_unprojectable_shape_rejected",
    "test_pp.py::test_pp_grad_clip_matches_optax[mesh_axes1-1-False]",
    "test_tp_pp.py::test_tp_pp_eval_forward_matches_apply",
    "test_pallas.py::test_model_pallas_backend_forward_parity",
    "test_train.py::test_pp_trainer_loop_path",
    "test_models.py::test_init_deterministic_across_calls",
    "test_accum_remat.py::test_grad_accum_matches_plain[data]",
    "test_accum_remat.py::test_grad_accum_matches_plain[data:4,model:2]",
    "test_accum_remat.py::test_remat_transformer_grads_match",
    "test_augment.py::test_trainer_augment_on_pp_mesh_is_deterministic",
    "test_ep.py::test_top2_moe_lm_trains",
    "test_ep.py::test_ep_layer_trains",
    "test_ep.py::test_dispatch_at_most_one_slot_per_token",
    "test_flash_attention.py::test_flash_bf16_gradients_match_oracle",
    "test_fsdp.py::test_fsdp_pp_matches_plain_pp[True-pipe:2,data:4]",
    "test_fsdp.py::test_fsdp_pp_matches_plain_pp[False-pipe:2,data:4]",
    "test_fsdp.py::test_lm_trainer_fsdp_and_fsdp_tp",
    "test_pp_lm.py::test_pp_lm_remat_matches_plain",
    "test_pp_lm.py::test_lm_pipeline_checkpoint_resume",
    "test_pp_lm.py::test_pp_lm_step_matches_serial[mesh_axes1]",
    "test_pp_lm.py::test_pp_lm_step_matches_serial[mesh_axes2]",
    "test_tp.py::test_lm_trainer_accepts_model_axis",
    "test_tp_sp.py::test_tp_sp_step_matches_serial[2-rope-mesh_axes1]",
    "test_tp_sp.py::test_tp_sp_step_matches_serial[0-learned-mesh_axes2]",
    "test_tp_sp.py::test_tp_sp_step_matches_serial[0-learned-mesh_axes3]",
    "test_generate.py::test_decode_matches_inference_forward_moe_top2",
    "test_generate.py::test_generate_shapes_and_budget",
    "test_gqa_rope.py::test_gqa_flash_gradients_match_oracle",
    "test_gqa_rope.py::test_lm_variants_train_and_decode[0-rope]",
    "test_lm.py::test_bf16_loss_close_to_f32",
    "test_lm.py::test_chunked_ce_matches_dense[bfloat16]",
    "test_pallas.py::test_conv_grad_parity[4-14-14-16-3-32-2-1]",
    "test_pp.py::test_pp_loss_and_grads_match_serial[4-4]",
    "test_step_resume.py::test_mid_epoch_resume_under_mesh[pipe:2,data:2]",
    "test_tp_pp.py::test_tp_pp_step_matches_serial[mesh_axes1-4]",
    "test_transformer.py::test_sp_step_parity_with_single_device[ulysses]",
    "test_digits.py::test_accuracy_on_real_digits",
    "test_dp.py::test_dp_composes_with_pallas_backend",
    "test_flash_attention.py::test_flash_gradients_match_oracle[256-False]",
    "test_flash_attention.py::test_flash_gradients_match_oracle[512-False]",
    "test_fsdp.py::test_fsdp_e2e_train_and_eval",
    "test_fsdp.py::test_fsdp_matches_replicated_dp[False]",
    "test_fsdp.py::test_fsdp_matches_replicated_dp[True]",
    "test_generate.py::test_decode_matches_inference_forward_moe",
    "test_generate.py::test_decode_matches_training_forward",
    "test_generate.py::test_moe_inference_routing_is_per_token",
    "test_generate.py::test_trained_model_generates_the_cycle",
    "test_models.py::test_presets_init_and_apply[cifar3conv]",
    "test_models.py::test_presets_init_and_apply[lenet5_relu]",
    "test_models.py::test_presets_init_and_apply[resnet8]",
    "test_models.py::test_presets_init_and_apply[vgg_small]",
    "test_models.py::test_residual_downsample_to_1x1",
    "test_models.py::test_residual_gradients_flow_through_shortcut",
    "test_models.py::test_residual_identity_vs_projection",
    "test_multihost.py::test_two_process_dp_step",
    "test_multihost.py::test_two_process_ring_sp_lm_step",
    "test_multihost.py::test_two_process_pipeline_step",
    "test_multihost.py::test_two_process_4d_lm_step",
    "test_accum_remat.py::test_lm_grad_accum_matches_plain",
    "test_tp_sp.py::test_tp_sp_ulysses_matches_serial",
    "test_ep.py::test_ep_dp_lm_trains",
    "test_accum_remat.py::test_sp_grad_accum_matches_plain",
    "test_tp_pp_lm.py::test_tp_pp_lm_moe_m1_matches_serial",
    "test_tp_sp.py::test_tp_sp_moe_trains",
    "test_pallas.py::test_conv_bf16_parity[4-14-14-16-3-32-2-1]",
    "test_pallas.py::test_conv_bf16_parity[4-28-28-1-3-16-2-1]",
    "test_pallas.py::test_model_pallas_backend_trains",
    "test_pp.py::test_pp_loss_and_grads_match_serial[2-4]",
    "test_train.py::test_checkpoint_resume",
    "test_train.py::test_convergence_cifar3conv",
    "test_train.py::test_determinism_same_seed",
    "test_train.py::test_irwin_hall_reference_config",
    "test_train.py::test_pp_bfloat16_training",
    "test_train.py::test_pp_checkpoint_resume",
    "test_train.py::test_pp_rejects_bfloat16_params",
    "test_train.py::test_pp_trainer_end_to_end",
    "test_train.py::test_pp_trainer_matches_dp",
    "test_train.py::test_scan_matches_per_batch_loop",
    "test_gqa_rope.py::test_gqa_rope_under_ring_flash_sp",
    "test_gqa_rope.py::test_gqa_rope_under_ring_sp",
    "test_gqa_rope.py::test_lm_variants_train_and_decode[2-learned]",
    "test_lm.py::test_flash_impl_matches_oracle_in_step",
    "test_lm.py::test_train_step_learns_cyclic_task",
    "test_lm_trainer.py::test_checkpoint_resume_continues_at_step",
    "test_lm_trainer.py::test_data_seq_mesh_with_moe",
    "test_lm_trainer.py::test_sp_mesh_learns_synthetic_cycle",
    "test_step_resume.py::test_mid_epoch_resume_is_bitwise_exact[True]",
    "test_tp_pp.py::test_tp_pp_replicated_upstream_layers_match_serial",
    "test_tp_pp.py::test_tp_pp_step_matches_serial[mesh_axes0-2]",
    "test_tp_pp.py::test_trainer_accepts_tp_pp_mesh",
    "test_transformer.py::test_moe_lm_trains_under_ring_sp",
    "test_transformer.py::test_sp_dp_mesh_composes",
    "test_transformer.py::test_sp_step_parity_ring_flash",
    "test_transformer.py::test_sp_lm_learns_cyclic_task",
    "test_transformer.py::test_sp_remat_composition",
    "test_transformer.py::test_sp_step_parity_with_single_device[ring]",
}


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run the tests listed in conftest.SLOW_TESTS",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    # A test named explicitly on the command line (::-qualified) always
    # runs; other args in the same invocation still get the skip.
    # Nodeids are rootdir-relative with forward slashes, while CLI args
    # may be absolute or cwd-relative paths — normalize the arg's path
    # part against rootdir so `pytest /abs/tests/test_x.py::name` matches
    # exactly that file's test and nothing sharing its basename.
    def _normalize(arg):
        path, sep, rest = arg.partition("::")
        rel = os.path.relpath(os.path.abspath(path), str(config.rootdir))
        return rel.replace(os.sep, "/") + sep + rest

    explicit = tuple(_normalize(a) for a in config.args if "::" in a)

    def named_explicitly(item):
        nid = item.nodeid
        return any(nid == a or nid.startswith(a + "[") for a in explicit)

    skip = pytest.mark.skip(reason="slow; use --runslow (make test_all)")
    matched = set()
    for item in items:
        key = item.nodeid.split("/")[-1]
        if key in SLOW_TESTS:
            matched.add(key)
            if not named_explicitly(item):
                item.add_marker(skip)
    # A renamed/reparametrized test would silently rejoin the fast suite;
    # flag stale entries loudly. (Partial collection runs see a subset, so
    # only check when the whole suite was collected.)
    if len(items) > len(SLOW_TESTS) * 3:
        stale = SLOW_TESTS - matched
        if stale:
            import warnings

            warnings.warn(f"SLOW_TESTS entries match no test: {sorted(stale)}",
                          stacklevel=2)


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual devices, got {len(devices)}"
    return devices


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
