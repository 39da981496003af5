"""Test env: deterministic CPU backend with an 8-device virtual mesh.

Mirrors the reference's strategy of running device-dependent tests on a
fake/emulated backend (SURVEY.md §4.6): sharding tests use
xla_force_host_platform_device_count instead of real chips.
Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# full-precision matmuls: numeric-gradient checks need loss evaluations
# accurate to f32, not the bf16-ish default
os.environ["JAX_DEFAULT_MATMUL_PRECISION"] = "highest"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# tier-1 compiles on the CPU backend and keeps nothing: the persistent
# compile cache (paddle_tpu/utils/compile_cache.py) is for the chip
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

# ---------------------------------------------------------------------------
# Test tiering (VERDICT r3 item 10): `-m quick` is the fast CI lane
# (< 5 min, every subsystem represented); `-m slow` the long tail.
# Everything not explicitly slow is auto-marked quick.
# ---------------------------------------------------------------------------

import pytest  # noqa: E402

# files that are slow end to end (multiprocess PS, pipeline equality
# matrices, sanitizer rebuilds, NAS search, native binaries, f64 grids)
_SLOW_FILES = {
    "test_nas.py", "test_pipeline.py", "test_sanitized_native.py",
    "test_dist_ps.py", "test_native_runner.py", "test_native_trainer.py",
    "test_grad_x64.py", "test_detection_models.py", "test_elastic.py",
    "test_transformer_scale.py", "test_native_capi.py",
}

# slow tests inside otherwise-quick files (>6s each in the r4 timing run;
# each subsystem keeps quick members)
_SLOW_PATTERNS = (
    "ring_attention", "ulysses", "cp_train_step",
    "vgg_builds", "transformer_nmt", "beam_search_decode_transformer",
    "resnet_cifar", "label_semantic", "deepfm_on_parameter",
    "machine_translation",
    "multiprocess", "qat_trains", "post_training_quantization",
    "moe_expert_parallel", "op_bench_cli", "imperative_resnet",
    "sa_beats_random", "deformablegroups", "tree_conv_single",
    "lenet_trains", "dygraph_extra_modules", "sparse_matches_dense",
    "linearchaincrf", "hsigmoid", "warpctc", "sparse_with_global_norm",
    "sensitive_pruner", "timeline_export", "ssdtrains",
)


@pytest.fixture
def tpu_plugin():
    """Path of libtpu's PJRT plug-in for the native C/C++ runner tests.
    Their child process opens the chip; this process is on the CPU (top
    of this file), so the chip is free for it. Skips on a machine with
    no TPU attached."""
    from jax._src import hardware_utils
    chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    if not chips:
        pytest.skip("no TPU on this machine")
    import libtpu
    return libtpu.get_library_path()


def pytest_collection_modifyitems(config, items):
    for item in items:
        fname = item.fspath.basename
        ident = item.nodeid.lower()
        if item.get_closest_marker("multichip") is not None:
            # the 8-device mesh matrices (serving tensor-parallel
            # identity sweeps etc.) run in their own lane —
            # tools/run_multichip_tests.sh `-m multichip` — and are
            # auto-slow so the tier-1 quick lane stays fast
            item.add_marker(pytest.mark.slow)
        elif fname in _SLOW_FILES or any(p in ident
                                         for p in _SLOW_PATTERNS):
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.quick)
