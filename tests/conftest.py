"""Test config: force a virtual 8-device CPU mesh so distributed logic is
CI-testable without TPUs (reference analog: fake_cpu_device.h pluggable
fake device — SURVEY.md §4)."""
import os

# Force CPU before any backend exists, and export the same so every
# subprocess the tests spawn (launch/elastic/rpc/ps workers, serving
# replicas) inherits it.  XLA_FLAGS is read at CPU client creation, so
# setting it here works.
import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# the backend here defaults matmuls to reduced precision; numeric-grad
# comparisons need true f32 matmuls
jax.config.update("jax_default_matmul_precision", "float32")

# Persistent XLA compilation cache: the suite is compile-bound (model-zoo
# CNNs alone cost minutes of XLA time); caching compiled executables
# across invocations brings repeat runs inside the driver's window.  The
# framework's own resolver places it (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache); the suite only raises the threshold back so
# its thousands of tiny eager-op programs are not each written to disk.
from paddle_tpu.core.op_cache import ensure_compile_cache  # noqa: E402

ensure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


# ---- fast/slow split so `pytest tests/ -q` fits the driver's window ----
# The box is single-core: the full suite costs ~26 min, dominated by a
# handful of compile/compute-heavy tests.  Those run only when
# PADDLE_TPU_RUN_SLOW=1 (tools/run_ci.sh sets it); the default run keeps
# at least one fast test per subsystem green in <~5 min.  Durations (s)
# from the r04 measurement on this box are noted inline.
_SLOW_TESTS = {
    # full zoo = 411s; light families (alexnet, squeezenet) stay fast
    "test_subpackage_parity.py::test_model_zoo_families_forward[vgg11]",
    "test_subpackage_parity.py::test_model_zoo_families_forward[densenet121]",
    "test_subpackage_parity.py::test_model_zoo_families_forward[inception_v3]",
    "test_subpackage_parity.py::test_model_zoo_families_forward[shufflenet_v2_x1_0]",
    "test_subpackage_parity.py::test_model_zoo_families_forward[mobilenet_v2]",
    "test_subpackage_parity.py::test_model_zoo_families_forward[mobilenet_v3_small]",
    "test_subpackage_parity.py::test_model_zoo_families_forward[mobilenet_v3_large]",
    "test_subpackage_parity.py::test_model_zoo_families_forward[resnext50_32x4d]",
    "test_subpackage_parity.py::test_model_zoo_families_forward[wide_resnet50_2]",
    "test_subpackage_parity.py::test_googlenet_aux_heads",
    "test_elastic_resume.py::test_kill_and_resume_matches_uninterrupted",  # 55
    "test_recompute.py::test_gpt_use_recompute_parity",            # 52
    "test_hapi_vision.py::test_resnet_and_mobilenet_forward",      # 51
    "test_moe.py::test_moe_expert_parallel_sharding",              # 38
    "test_hapi_vision.py::test_model_fit_decreases_loss",          # 32
    "test_generation.py::test_cached_generation_matches_full_forward[gpt]",    # 31
    "test_generation.py::test_cached_generation_matches_full_forward[llama]",  # 22
    "test_generation.py::test_gqa_cache_holds_kv_heads_only",      # 25
    "test_comm_budget.py::test_tp_model_budget_axes_and_roofline", # 22
    "test_subpackage_parity.py::test_fused_layers_forward_and_train",  # 21
    "test_moe.py::test_moe_grad_clip_api",                         # 18
    "test_context_parallel.py::test_ring_attention_backward",      # 16
    "test_pallas_kernels.py::test_flash_dropout_gqa_matches_dense_hash[False]",  # 16
    "test_pallas_kernels.py::test_flash_dropout_gqa_matches_dense_hash[True]",   # 10
    "test_llama.py::test_eager_trains",                            # 14
    "test_moe.py::test_moe_layer_forward_backward",                # 27
    "test_moe.py::test_moe_parallel_matches_single_device",        # 26
    "test_auto_tuner.py::test_tune_by_launch_runs_real_trials",    # 13
    "test_moe.py::test_moe_ep_dp_hybrid_matches_replicated",       # 12
    "test_nn_extra.py::test_ctc_loss_matches_torch",               # 12
    "test_auto_parallel_engine.py::test_engine_plan_trial_confirms_pp",  # 90
    "test_inference_capi.py::test_c_api_predicts_from_c_host",  # embeds py
    "test_hapi_vision.py::test_hapi_distributed_fit_two_procs",  # 2 procs
    # r04 generation additions: growing-shape full-forward loops compile
    # per step — correctness stays covered by the fast sampled/eos tests
    "test_generation.py::test_beam_search_beats_or_matches_greedy",  # 34
    "test_generation.py::test_beam_search_length_penalty_and_validation",
    "test_generation.py::test_cached_and_full_forward_agree_with_processors",
    "test_generation.py::test_top_p_tight_equals_greedy",          # 14
    "test_subpackage_parity.py::test_model_zoo_families_forward[squeezenet1_0]",  # 13; alexnet stays as the fast zoo representative
    # r05 re-fit (VERDICT r04 weak #3: the lane outgrew its ~520s budget):
    # each move keeps at least one fast test per subsystem — hapi keeps
    # fit/predict + weights-cache, llama keeps gqa/eager, generation keeps
    # sampled + eos, int8 keeps the dynamic-quant tests, property keeps
    # reductions, book keeps recognize_digits, collectives stay covered by
    # test_distributed + the tcp_store rendezvous
    "test_hapi_vision.py::test_model_prepare_amp_o1_and_o2",       # 24
    "test_llama.py::test_parallel_llama_matches_serial",           # 24
    "test_multiproc.py::test_two_process_collectives",             # 20
    "test_generation.py::test_generation_respects_max_seq_len",    # 17
    "test_generation.py::test_repetition_penalty_breaks_loops",    # 15
    "test_static_inference.py::test_int8_baked_export_ptq_gpt_block",  # 15
    "test_hapi_vision.py::test_early_stopping",                    # 15
    "test_property_ops.py::test_elementwise_grads_sum_rule",       # 14
    "test_property_ops.py::test_manipulation_round_trips",         # 11
    "test_book.py::test_word2vec_book",                            # 13
    "test_nn.py::test_grid_sample",                                # 12
    "test_tcp_store.py::test_master_rendezvous_across_processes",  # 17; 7 other tcp_store tests stay fast
    "test_pipeline.py::test_pipeline_train_batch_matches_grad_accumulation",  # 13; hetero + schedule tests keep pp fast coverage
    "test_onnx_export.py::test_onnx_zoo_exports_and_reimports[alexnet]",  # 13; pooling/gpt round-trips stay fast
    "test_onnx_export.py::test_onnx_zoo_exports_and_reimports[resnet18]",
    "test_onnx_export.py::test_onnx_zoo_exports_and_reimports[mobilenet_v2]",
    # r06 guardian 2-proc subprocess drills (~20s each; the CI hang-drill
    # gate and the fast unit/SIGTERM tests keep tier-1 coverage)
    "test_guardian.py::test_collective_delay_stall_dump",
    "test_guardian.py::test_rank_crash_relaunch_resume_matches_uninterrupted",
    # r11 audit of the slowest tier-1 subprocess drills (ISSUE 11
    # housekeeping; durations from the r11 measurement on this box).
    # Every move keeps coverage elsewhere: the resize drills have a
    # dedicated run_ci.sh lane (PADDLE_TPU_RUN_SLOW=1) plus the full
    # RUN_SLOW suite, the sentinel/fault/train-step/elastic drills run
    # in the RUN_SLOW full suite and their fast in-process siblings
    # stay tier-1.
    "test_reshard.py::test_resize_4_to_2_drill",                   # 14
    "test_reshard.py::test_resize_2_to_4_drill",                   # 14
    "test_sentinel.py::test_blame_drill_two_procs",                # 6
    "test_fault_tolerance.py::test_drill_sigterm_preemption_relaunch_resumes",  # 5
    "test_train_step.py::test_dp_psum_matches_two_proc_sync_grads_drill",       # 5
    "test_launch_elastic.py::test_scale_in_dead_pod_triggers_rebuild",          # 5
    # r20 hot-spare recovery drills (2-proc controller relaunch each;
    # run_ci.sh runs the peer-restore drill in its own bounded lane and
    # the fast in-process ladder tests stay tier-1)
    "test_hot_spare.py::test_hot_spare_drill_peer_restore",
    "test_hot_spare.py::test_hot_spare_drill_buddy_crash_falls_to_disk",
}


@pytest.fixture(scope="session")
def api_spec():
    """The public surface the repository freezes, as
    {module name: {name: {"kind": ...}}}: tools/api_spec.json, the spec
    tests/test_api_gate.py enforces.  The ``*_parity`` tests take their
    expected names from it."""
    import json
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "api_spec.json")
    with open(path) as f:
        return json.load(f)


def pytest_collection_modifyitems(config, items):
    if os.environ.get("PADDLE_TPU_RUN_SLOW"):
        return
    skip = pytest.mark.skip(
        reason="slow test; set PADDLE_TPU_RUN_SLOW=1 (tools/run_ci.sh "
               "does) to run")
    for item in items:
        rel = "/".join(item.nodeid.split("/")[-1:])
        if rel in _SLOW_TESTS:
            item.add_marker(skip)
