"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy of faking a cluster on one host
(reference: tests/distributed/_test_distributed.py spawns N localhost
processes); here N virtual XLA host devices stand in for N TPU chips.
Must run before jax initializes.
"""
import os

# force-set: the tests run on the virtual CPU mesh whatever the ambient
# environment selects
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORM_NAME"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


@pytest.fixture
def timer_on():
    """The registry's stage timer, on for one test: counters and compile
    accounting that are kept only under ``obs.enabled`` move."""
    from lightgbm_tpu.obs.registry import registry
    was = registry.timer.enabled
    registry.timer.enable()
    try:
        yield registry.timer
    finally:
        registry.timer.enabled = was
