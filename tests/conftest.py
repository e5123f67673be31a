"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-device sharding tests run without TPU hardware via
``--xla_force_host_platform_device_count`` (the TPU answer to testing multi-chip
topologies on one host). Env vars must be set before jax is imported anywhere.
"""

import os

# hard override: tests always run on the virtual CPU mesh, whatever the
# session environment names
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (prev + " --xla_force_host_platform_device_count=8").strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pytest

_REPO_SAMPLE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "sample")
_SAMPLE_DIR = _REPO_SAMPLE if os.path.isdir(_REPO_SAMPLE) else "/root/reference/sample"
SAMPLE_VIDEO = os.path.join(_SAMPLE_DIR, "v_GGSY1Qvo990.mp4")
SAMPLE_VIDEO_2 = os.path.join(_SAMPLE_DIR, "v_ZNVhz7ctTq0.mp4")


@pytest.fixture(scope="session")
def sample_video():
    if not os.path.exists(SAMPLE_VIDEO):
        pytest.skip("sample video unavailable")
    return SAMPLE_VIDEO


@pytest.fixture(scope="session")
def sample_video_2():
    if not os.path.exists(SAMPLE_VIDEO_2):
        pytest.skip("sample video unavailable")
    return SAMPLE_VIDEO_2


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# ---- test tiers -----------------------------------------------------------
# fast: the pure-math/unit layer — `pytest -m fast` gives pre-commit signal in
# under a minute on a 1-core host (round-4 review: the full non-slow tier no
# longer fits a quick review budget). Membership is by module (measured
# per-module wall times, /tmp-tier sweep round 5); new quick modules should be
# added here. `slow` stays the parity/e2e layer; everything else is the
# default `not slow` tier.
_FAST_MODULES = {
    "test_async_writer",
    "test_cache",
    "test_config_cli",
    "test_edge_cases",
    "test_fault_barrier_lint",
    "test_filelist_output",
    "test_flow_sharded",
    "test_fps_resampler",
    "test_golden_pipeline",
    "test_ingest",
    "test_mirror_independence",
    "test_multimodel",
    "test_obs",
    "test_packer",
    "test_packer_buckets",
    "test_parallel",
    "test_reliability",
    "test_resample",
    "test_resnet_extractor",
    "test_service",
    "test_setup_metrics",
    "test_spatial",
    "test_vftlint",
    "test_video_decode",
    "test_wal",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (item.module.__name__ in _FAST_MODULES
                and "slow" not in item.keywords):
            item.add_marker(pytest.mark.fast)
