"""Test harness: force an 8-device virtual CPU mesh so sharding/distribution
tests run anywhere (the driver's multichip dryrun uses the same mechanism).

The suite is a CPU suite: the platform and the device count are pinned
here, before the first backend query, whatever the caller's environment.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); the "
        "dist-smoke/CI gates cover these paths every run")


def make_oom_adaptor(impl: str, limit: int = 1000):
    """Shared python-or-native adaptor factory for the differential OOM
    state-machine suites (skips when the native build is unavailable)."""
    import pytest
    from spark_rapids_tpu.memory.resource import LimitingMemoryResource
    from spark_rapids_tpu.memory.spark_resource_adaptor import \
        SparkResourceAdaptor
    if impl == "python":
        return SparkResourceAdaptor(LimitingMemoryResource(limit))
    from spark_rapids_tpu.memory import native_adaptor
    if not native_adaptor.available():
        pytest.skip("native adaptor unavailable (g++ build failed)")
    return native_adaptor.NativeSparkResourceAdaptor(limit)
