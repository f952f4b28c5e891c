"""Test configuration: run everything on a virtual 8-device CPU mesh.

This is the TPU-world stand-in for a multi-chip testbed (SURVEY.md
section 4): ``xla_force_host_platform_device_count`` fakes 8 devices so
sharding/collective tests run on one host. Must be set before jax is
imported anywhere.
"""

import gc
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

assert jax.default_backend() == "cpu", "tests must run on the virtual CPU mesh"
assert jax.device_count() == 8, "expected 8 virtual CPU devices for sharding tests"

import pytest  # noqa: E402

# Test tiers (VERDICT r1 item 8): ``pytest -m quick`` is the <3-minute
# smoke pass; the default (no -m) runs everything (~23 min on an 8-core
# host, dominated by interpreter-mode Pallas parity and end-to-end
# trainer tests). Membership is by nodeid substring: the patterns below
# name the measured-slow tests/classes/modules (--durations=40 run,
# 2026-07-30); everything else is marked quick.
_SLOW_PATTERNS = (
    "test_multihost_2proc.py",
    "test_pipeline.py",
    "test_remat.py",
    "test_runtime.py::TestEndToEnd",
    "test_parallel.py::TestShardedStep",
    "test_parallel.py::TestShardedTraining",
    "test_parallel.py::TestShardFlash",
    "test_decode.py",
    "test_flash_models.py",
    "test_train.py::TestTrainStep::test_loss_decreases_all_models",
    "test_train.py::TestTrainStep::test_grad_accumulation_matches_big_batch",
    "test_ring.py::test_sharded_train_step_with_sequence_axis",
    "test_ring.py::test_ring_flash",
    "test_losses.py::TestModelLossChunk",
    "test_models.py::TestInitAndShapes::test_init_statistics",
    "test_flash.py::test_ndiff_grad_parity",
    "test_flash.py::test_diff_grad_parity",
    "test_flash.py::test_vjp",
    "test_torch_import.py",
    "test_torch_export.py",
    # ulysses: the model-forward/train-step/dropout tests are slow; the
    # bare-op parity tests (diff/ndiff/tensor-axis/uneven-heads, each a
    # few seconds) stay in the quick smoke pass
    "test_ulysses.py::test_ulysses_train_step",
    "test_ulysses.py::test_model_forward_ulysses",
    "test_ulysses.py::test_ulysses_pallas_dropout",
    "test_ulysses.py::test_ulysses_dropout",
    "test_ulysses.py::test_ulysses_grad_parity",
    "test_ulysses.py::test_vanilla_ulysses_parity",
    "test_flash_dropout.py::test_grad_matches_dense_with_same_masks",
    "test_flash_dropout.py::test_tiled_kernels_match_dense_with_same_masks",
    "test_flash_dropout.py::test_model_forward_with_fused_dropout",
    "test_ring.py::TestRingDropout::test_mean_preservation",
    "test_ring.py::TestRingDropout::test_grads_flow",
)


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop every compiled program when a test module is done.

    Each XLA:CPU executable holds a few memory mappings for as long as
    some jit cache holds it, and the serving engine's step builders are
    cached at module level. One serial run of the quick tier otherwise
    accumulates past the kernel's vm.max_map_count (65530) about 85% of
    the way through, and the next compile dies with a segmentation
    fault. Modules share next to no programs, so this costs little."""
    yield
    jax.clear_caches()
    gc.collect()


def pytest_collection_modifyitems(config, items):
    for item in items:
        # explicit @pytest.mark.slow decorators (e.g. the multi-second
        # serving tests, test_serving.py) count like pattern membership
        if any(pat in item.nodeid for pat in _SLOW_PATTERNS):
            item.add_marker(pytest.mark.slow)
        elif item.get_closest_marker("slow") is None:
            item.add_marker(pytest.mark.quick)
