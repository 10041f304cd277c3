"""Test harness config.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding paths
(Mesh/shard_map) are exercised without TPU hardware. The platform is pinned
through jax.config as well as the environment, before any backend is
initialized, so a host whose site configuration selects another backend
still runs the suite on the CPU.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# the CLI places JAX's persistent compilation cache at a fixed path inside
# the checkout (trivy_tpu.enable_compile_cache); test workers compile tiny
# CPU programs and must not share or grow that on-disk cache
jax.config.update("jax_enable_compilation_cache", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: minutes-long case, left out of tier-1 (-m 'not slow')"
    )
