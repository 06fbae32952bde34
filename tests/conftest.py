"""Test configuration: the suite runs on the CPU platform with 8
virtual devices, so multi-chip sharding (jax.sharding.Mesh over key
groups) is exercised without TPU hardware.  Both variables must be in
the environment before jax is imported.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos sweeps excluded from the tier-1 run "
        "(-m 'not slow')")
