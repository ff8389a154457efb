"""Test harness: simulate an 8-device mesh on host CPU.

The TPU-native analog of the reference's ``mp.spawn``-on-localhost pattern
(`model_parallel_ResNet50.py:260` — SURVEY.md §4): a multi-device topology
exercisable on one host, so mesh/sharding/checkpoint/elastic code runs in CI
without a TPU.  Chip coverage lives in ``chip_smoke.py`` (run separately; it
owns the chip for the duration) and the AOT compiles of
``test_aot_tpu_compile.py`` — unit tests never touch a chip:
``force_cpu_devices`` pins the CPU platform before jax initializes.
"""

import os

# The persistent-cache AOT loader logs a full machine-feature dump at E
# level for XLA's prefer-no-scatter/gather PSEUDO-features on every cache
# hit (same machine, no real ISA mismatch) — silence the C++ log stream
# before jax loads; Python exceptions still propagate normally.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
# The suite builds the same tiny programs again and again (every ServeLoop
# or step factory is a fresh jax.jit, and so is every worker subprocess):
# let the persistent cache keep sub-second compiles too.  Read by jax at
# import, here and in the children.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

from tpudist.runtime.simulate import force_cpu_devices  # noqa: E402

force_cpu_devices(8)

import jax  # noqa: E402
import pytest  # noqa: E402

from tpudist.runtime.cache import enable_compilation_cache  # noqa: E402

# Persistent compilation cache across test runs (round-4 verdict #9: the
# default suite's budget is dominated by CPU-backend compiles of the
# deep-rollout tests; measured 5.7 s -> 0.9 s on a warm 4-layer rollout).
# Worker subprocesses inherit it via the variable JAX itself reads.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", enable_compilation_cache())


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest must run before any jax import"
    return devs[:8]


# ---- test lanes (round-4 verdict #9) ------------------------------------
#
# The DEFAULT lane (`pytest`) is the quick signal: every subsystem keeps
# its fast correctness tests.  The SLOW lane (`pytest -m slow`) holds the
# multi-process elastic/distributed suites (marked at module level) plus
# the heavyweight parity tests below — each individually measured ≥ ~10 s
# of CPU-interpret execution (`--durations`), with a faster sibling
# covering the same subsystem in the default lane.  CI / the round driver
# should run BOTH: `pytest && pytest -m slow`.
_HEAVY = (
    "test_pipeline.py::TestPackedPipeline::"
    "test_resnet50_two_stage_packed_trains",
    "test_pipeline.py::TestResNet50Pipeline::test_two_stage_resnet_trains",
    "test_models.py::test_resnet50_stage_split",
    "test_models.py::test_transformer_remat_matches_plain",
    "test_models.py::test_resnet50_full_model_matches_two_stage_depth",
    "test_generate.py::test_cached_decode_matches_full_forward",
    "test_generate.py::TestFlashDecode::test_sp_flash_decode_in_shard_map",
    "test_generate.py::test_tp_sp_generate_2d_sharded_decode",
    "test_generate.py::test_windowed_model_decode_matches_windowed_forward",
    "test_generate.py::TestPerRowFlashDecode::"
    "test_matches_scalar_per_row[2-128]",
    "test_generate.py::test_generate_gqa_cache_is_grouped",
    "test_generate.py::TestInt8PairedDecode::"
    "test_q8_accuracy_vs_bf16[2-64-None]",
    "test_generate.py::TestFlashDecode::"
    "test_chunked_prefill_matches_one_shot",
    "test_speculative.py::TestSampling::"
    "test_rollout_marginal_matches_plain_sampling",
    "test_speculative.py::TestSampling::test_matches_vocab_range",
    "test_speculative.py::TestGreedyExactness::test_matches_greedy_any_draft",
    "test_speculative.py::TestAcceptRule::"
    "test_output_distribution_is_target",
    "test_speculative.py::TestAdaptiveDraftPolicy::"
    "test_plain_probe_arms_gate_and_stays_exact",
    "test_speculative.py::TestAdaptiveDraftPolicy::"
    "test_adaptive_rollout_exactness_and_adaptation",
    "test_speculative.py::TestTensorParallel::"
    "test_tp_speculative_matches_unsharded",
    "test_examples.py::test_serve_continuous_example",
    "test_examples.py::test_mnist_horovod_twin",
    "test_examples.py::test_long_context_lm_generation_demo[extra3]",
    "test_examples.py::test_long_context_lm_twin[extra0]",
    "test_moe.py::test_ep_shard_step_all_to_all_and_matches_dense",
    "test_moe.py::test_moe_lm_ep_train_step_on_mesh",
    "test_moe.py::TestFusedDispatch::test_skewed_routing",
    "test_moe.py::TestFusedDispatch::test_gradients_match_ragged",
    "test_moe.py::TestRaggedDispatch::test_matches_einsum_when_no_drops",
    "test_moe.py::TestRaggedDispatch::test_lm_end_to_end",
    "test_serving.py::TestParity::test_mixed_lengths_and_slot_reuse",
    "test_serving.py::TestPadCapRegression::"
    "test_prompt_near_cache_end_with_nondividing_chunk",
    "test_serving.py::TestStopAndBudget::test_stop_token_completion",
    "test_scan_layers.py::TestSpeculative::test_scanned_target_and_draft",
    "test_scan_layers.py::TestParity::test_gradients",
    "test_scan_layers.py::TestParity::test_greedy_decode",
    "test_ring_attention.py::test_sp_train_step_matches_single_device",
    "test_group_norm.py::test_matches_flax_forward_and_grads",
    "test_group_norm.py::test_resnet_group_matches_flax_group_training_step",
    "test_group_norm.py::TestFusedKernels::test_relu_mode",
    "test_tensor_parallel.py::test_tp_matches_single_device",
    "test_beam.py::TestBeamSearch::test_beats_or_matches_greedy[0]",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        nid = item.nodeid
        base = nid.split("[")[0]
        for h in _HEAVY:
            if nid.endswith(h) or ("[" not in h and base.endswith(h)):
                item.add_marker(pytest.mark.slow)
                break
