"""Test configuration.

Tests run on a virtual 8-device CPU mesh so that every sharding/collective
code path is exercised without TPU hardware (the driver separately dry-runs
the multi-chip path; benchmarks/run.py and chip_smoke.py run on the real chip).

The env vars MUST be set before jax is imported anywhere.
"""

from redpanda_tpu.utils.platform import force_cpu_platform

# sets JAX_PLATFORMS=cpu and the 8-virtual-device XLA flag, then pins the
# live jax config; nothing imported above this line imports jax
force_cpu_platform(8)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual cpu devices, got {len(devs)}"
    return devs[:8]
