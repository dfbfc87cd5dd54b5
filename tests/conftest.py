"""Test fixtures: force an 8-device virtual CPU mesh so multi-chip sharding
paths are exercised without TPU hardware (SURVEY.md §4 fixtures: the TPU
analog of the reference's local-process fake cluster), and pin matmul
precision to float32 so numeric checks are meaningful (TPU-default bf16
passes are a perf feature, not a correctness one).
"""
import os
import tempfile

os.environ['JAX_PLATFORMS'] = 'cpu'
# in-process preemption/stall tests escalate through the flight
# recorder (docs/OBSERVABILITY.md); keep their dumps out of the repo
os.environ.setdefault(
    'MXNET_TPU_FLIGHT_PATH',
    os.path.join(tempfile.gettempdir(), 'mxnet_tpu_test_FLIGHT.jsonl'))
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()

import jax  # noqa: E402

jax.config.update('jax_default_matmul_precision', 'float32')

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# registry names present at session start: tests that register plugin /
# custom ops mid-session must not shift op-sweep coverage accounting
import mxnet_tpu  # noqa: E402
from mxnet_tpu.ops import registry as _op_registry  # noqa: E402
BASELINE_OPS = frozenset(_op_registry.OPS)

# ---------------------------------------------------------------------------
# Test tiers (reference analog: the unittest / nightly split, SURVEY §4).
# Files listed here are the long-running sweeps; everything else is the
# fast smoke tier. Run `pytest -m fast` for a <5-minute gate on a 1-core
# host, plain `pytest` for the full suite (~12 min on the bench host).
# ---------------------------------------------------------------------------
SLOW_TEST_FILES = {
    'test_op_sweep.py',          # FD gradient check over the whole registry
    'test_onnx_conformance.py',  # ONNX model round-trip corpus
    'test_examples.py',          # runs every example workload end-to-end
    'test_contrib_onnx_quant.py',
    'test_im2rec.py',            # packs/reads record files on disk
    'test_image_ssd.py',         # detection pipeline + NMS kernels
    'test_transformer.py',       # full transformer fwd/bwd stacks
    'test_ring_attention.py',    # ring/Ulysses vs dense oracle sweeps
    'test_fused_step.py',        # whole-model fused train steps
    'test_multidevice.py',       # 8-device pjit compiles
    'test_optimizer_numerics.py',  # every optimizer vs oracle
    'test_rewrites.py',          # model-zoo forwards (~100 s of compiles)
}


def pytest_configure(config):
    config.addinivalue_line('markers', 'slow: long-running sweep/e2e test')
    config.addinivalue_line('markers', 'fast: smoke-tier test (default)')


def pytest_collection_modifyitems(config, items):
    for item in items:
        slow = (item.fspath.basename in SLOW_TEST_FILES
                or item.get_closest_marker('slow') is not None)
        item.add_marker(pytest.mark.slow if slow else pytest.mark.fast)


@pytest.fixture(autouse=True)
def _seed_rngs():
    """with_seed() parity (reference: tests/python/unittest/common.py:117).
    Also resets the auto-naming counters so symbol names (convolution0_...)
    are deterministic per test."""
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    mx.name.NameManager._current.value = mx.name.NameManager()
    yield
